import os
# the production mesh is emulated on 512 host devices: pin the CPU backend,
# or on a TPU host jax would take the chip and find one device
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape), lower + compile the step on the
production mesh — 16x16 (single pod) and 2x16x16 (two pods) — and record:
  * memory_analysis (bytes per device: argument/output/temp/generated code)
  * cost_analysis (FLOPs, bytes accessed)
  * loop-aware collective bytes (per device), split by mesh axis
  * the roofline terms (compute / memory / collective seconds, v5e constants)

Results are cached as JSON under --out so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--plan agents-data]
"""
import argparse
import json
import time
import traceback


def _roofline(flops, hbm_bytes, coll_bytes_by_axis):
    from repro.launch.mesh import DCI_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = hbm_bytes / HBM_BW
    ici = coll_bytes_by_axis.get("model", 0) + coll_bytes_by_axis.get("other", 0)
    dci = coll_bytes_by_axis.get("agent", 0)
    # agent-axis traffic crosses pods in the multi-pod mesh; single-pod it is
    # ICI too — we report both the ICI-only and the DCI-penalised variants.
    collective_s = ici / ICI_BW + dci / ICI_BW
    collective_s_dci = ici / ICI_BW + dci / DCI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s, "collective_s_dci": collective_s_dci}
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["dominant"] = dom
    return terms


def run_pair(arch: str, shape_name: str, *, multi_pod: bool, plan: str = "agents-data",
             mode: str = "fedgan", K: int = 20, ring_cache: bool = False,
             fsdp: bool = False, sync_dtype: str = "", intra: int = 0,
             save_hlo: str = "", analyze: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_shape, pair_supported
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_production_mesh, mesh_dims
    from repro.launch.steps import PLANS, build_step, round_donation

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = pair_supported(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "plan": plan, "mode": mode, "ring_cache": ring_cache, "fsdp": fsdp,
           "sync_dtype": sync_dtype, "intra_interval": intra}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    kw = {}
    if shape.kind == "train":
        kw = dict(plan=PLANS[plan], K=K, mode=mode,
                  sync_dtype=jnp.bfloat16 if sync_dtype == "bf16" else None,
                  intra_interval=intra)
    elif shape.kind == "decode":
        kw = dict(ring_cache=ring_cache, fsdp=fsdp)
    else:
        kw = dict(fsdp=fsdp)

    t0 = time.time()
    built = build_step(cfg, shape, mesh, **kw)
    if analyze:
        from repro.analysis.trace import audit_built
        rec["analysis"] = [f.to_json() for f in audit_built(
            built, donate_argnums=round_donation(built))]
    with jax.set_mesh(mesh):
        # donate the round state — without this the compiled module keeps
        # two copies of params+opt live (alias_size_in_bytes was 0)
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=round_donation(built))
        lowered = jitted.lower(*built.input_sds)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    from repro.launch.hlo_analysis import program_costs

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k, 0)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}
    mem["total_hbm_bytes"] = (mem["argument_size_in_bytes"]
                              + mem["temp_size_in_bytes"]
                              + mem["generated_code_size_in_bytes"]
                              + mem["output_size_in_bytes"]
                              - mem.get("alias_size_in_bytes", 0))
    ca = compiled.cost_analysis() or {}

    txt = compiled.as_text()
    # loop-aware per-device accounting (cost_analysis counts while bodies
    # once — verified; see hlo_analysis docstring)
    pc = program_costs(txt)
    flops = float(pc["flops"])
    bytes_accessed = float(pc["hbm_bytes"])
    stats = collective_bytes(txt)
    by_axis = stats.bytes_by_axis(mesh_dims(mesh))
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(txt)

    steps_per_call = K if shape.kind == "train" else 1
    roof = _roofline(flops / steps_per_call, bytes_accessed / steps_per_call,
                     {k: v / steps_per_call for k, v in by_axis.items()})

    rec.update(
        status="ok",
        mesh="2x16x16" if multi_pod else "16x16",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=mem,
        flops=flops, bytes_accessed=bytes_accessed,
        xla_cost_analysis={k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))},
        steps_per_call=steps_per_call,
        collectives=stats.summary(),
        collective_by_axis=by_axis,
        roofline_per_step=roof,
        meta={k: v for k, v in built.meta.items() if k != "state_specs"},
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--plan", default="agents-data")
    ap.add_argument("--mode", default="fedgan")
    ap.add_argument("--K", type=int, default=20)
    ap.add_argument("--ring-cache", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--sync-dtype", default="")
    ap.add_argument("--intra", type=int, default=0)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--analyze", action="store_true",
                    help="run the repro.analysis trace auditor on each "
                         "built step and record findings in the JSON")
    args = ap.parse_args()

    from repro.configs import list_archs
    from repro.models.config import SHAPES

    os.makedirs(args.out, exist_ok=True)
    pairs = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    for arch, shape, mp in pairs:
        key = f"{args.tag}__{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
        path = os.path.join(args.out, key + ".json")
        if os.path.exists(path):
            print(f"[cached] {key}")
            continue
        print(f"[run]    {key} ...", flush=True)
        try:
            rec = run_pair(arch, shape, multi_pod=mp, plan=args.plan,
                           mode=args.mode, K=args.K, ring_cache=args.ring_cache,
                           fsdp=args.fsdp, sync_dtype=args.sync_dtype,
                           intra=args.intra, save_hlo=args.save_hlo,
                           analyze=args.analyze)
        except Exception as e:  # record failures — they are bugs to fix
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline_per_step"]
            extra = (f" compile={rec['compile_s']}s dom={r['dominant']}"
                     f" c={r['compute_s']*1e3:.2f}ms m={r['memory_s']*1e3:.2f}ms"
                     f" coll={r['collective_s']*1e3:.2f}ms"
                     f" hbm/dev={rec['memory']['total_hbm_bytes']/2**30:.2f}GiB")
        elif status == "error":
            extra = " " + rec["error"][:160]
        else:
            extra = " " + rec.get("reason", "")
        print(f"[{status}] {key}{extra}", flush=True)


if __name__ == "__main__":
    main()
