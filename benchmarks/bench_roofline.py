"""Roofline rows: standalone per-kernel mode + dry-run post-processing.

Two row families, both machine-readable through ``run.py --json``
(BENCH_roofline.json — part of the committed perf trajectory):

  * ``roofline_kernel_*`` — standalone per-kernel rows that need NO prior
    dry-run: each sync-hot-path kernel (qpack pack/unpack, fedavg reduce,
    fused qsync, fused adam+sync) timed on the path ``use_kernel=None``
    actually picks on this backend, with achieved GB/s and elems/s against
    a measured copy roofline (a jitted saxpy stream on the same host — the
    roofline's memory term, since every one of these kernels is
    memory-bound by construction).  ``roofline_frac`` is achieved GB/s over
    stream GB/s; ``memory_term_s`` is the bytes-over-stream-bandwidth floor
    the kernel cannot beat.
  * ``roofline_<arch>_*`` — the original (g) deliverable: roofline terms /
    useful-FLOPs ratio / HBM occupancy post-processed from the dry-run
    artifacts when ``results/dryrun`` exists (unchanged; absent artifacts
    now skip quietly instead of being the suite's only output).

``roofline_fused_vs_composed`` measures the tentpole directly: one bucketed
fused ``coded_sync`` dispatch chain vs the per-leaf composed pipeline on the
same tree, wall-clock AND quantize-site counts from the lowered jaxprs.
NOTE the CI gate is on the dispatch counts (fused = 2 quantize sites per
round regardless of leaf count; composed = 2·leaves), not wall-clock: on
this 2-core CPU container both paths run the vectorized ref, where
bucketing wins ~1.3x on many-small-leaf trees but the concat copies can
eat the win on huge leaves — the HBM-traffic win the fusion exists for
(no per-agent wire image materialized) only shows on a real TPU backend.
"""
from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.configs import get_config
from repro.models.transformer import Backbone


# ---------------------------------------------------------------- kernels

def _count_round_sites(fn, *args) -> int:
    """Quantize sites in fn's jaxpr = number of `round` primitives,
    recursing through scan/cond/pjit sub-jaxprs."""
    from jax.extend import core as jex_core

    def walk(jaxpr) -> int:
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "round":
                total += 1
            for v in eqn.params.values():
                if isinstance(v, jex_core.ClosedJaxpr):
                    total += walk(v.jaxpr)
                elif isinstance(v, jex_core.Jaxpr):
                    total += walk(v)
        return total

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _stream_gbps(fast=False) -> float:
    """Measured copy roofline: bytes/s of a jitted saxpy over a stream that
    dwarfs cache — the memory-bandwidth ceiling the kernel rows are scored
    against on THIS host."""
    n = 1 << 22 if fast else 1 << 24
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    _, us = timed(jax.jit(lambda v: v * 1.5 + 2.0), x, iters=5)
    return 2 * 4 * n / (us / 1e6) / 1e9  # read + write


def _kernel_row(name, us, n_elems, n_bytes, stream, **extra):
    gbps = n_bytes / (us / 1e6) / 1e9
    emit(f"roofline_kernel_{name}", us,
         f"GBps={gbps:.2f};elems_per_s={n_elems / (us / 1e6):.3e};"
         f"roofline_frac={gbps / stream:.2f}",
         gb_per_s=round(gbps, 3),
         elems_per_s=round(n_elems / (us / 1e6), 1),
         bytes_touched=n_bytes,
         roofline_frac=round(gbps / stream, 3),
         memory_term_s=round(n_bytes / (stream * 1e9), 6),
         backend=jax.default_backend(), **extra)


def bench_kernel_rooflines(fast=False):
    from repro.kernels.fedavg.ref import fedavg_flat_ref
    from repro.kernels.qpack.ops import (_use_kernel_default,
                                         dequantize_blocks, quantize_blocks)
    from repro.kernels.qsync import ops as qsync_ops

    stream = _stream_gbps(fast=fast)
    emit("roofline_stream", 0.0, f"stream_GBps={stream:.2f}",
         stream_gb_per_s=round(stream, 3), backend=jax.default_backend())

    B, n = 16, (1 << 14 if fast else 1 << 16)
    path = "kernel" if _use_kernel_default() else "ref"
    x = jax.random.normal(jax.random.key(0), (B, n), jnp.float32)
    w = jax.random.dirichlet(jax.random.key(1), jnp.ones(B))

    # qpack pack: read f32, write int8 codes (int4: packed nibbles) + scales
    for bits in (8, 4):
        enc = jax.jit(lambda v, b=bits: quantize_blocks(v, bits=b))
        (q, s), us = timed(enc, x)
        nb = (4 * B * n + B * n * bits // 8
              + s.size * s.dtype.itemsize)
        _kernel_row(f"qpack_pack_int{bits}", us, B * n, nb, stream,
                    path=path)
        dec = jax.jit(lambda qq, ss, b=bits: dequantize_blocks(
            qq, ss, n=n, bits=b))
        _, us_d = timed(dec, q, s)
        _kernel_row(f"qpack_unpack_int{bits}", us_d, B * n, nb, stream,
                    path=path)

    # fedavg: read stacked f32 + weights, write the (n,) average
    _, us = timed(jax.jit(fedavg_flat_ref), w, x)
    _kernel_row("fedavg", us, B * n, 4 * B * n + 4 * B + 4 * n, stream,
                path="ref")

    # fused qsync: read stacked + both residuals, write synced + residuals —
    # the per-agent wire image is the traffic the fusion does NOT pay
    ef = jnp.zeros_like(x)
    efd = jnp.zeros((n,), jnp.float32)
    for bits in (8, 4):
        f = jax.jit(lambda t, e, d, b=bits: qsync_ops.qsync_flat(
            w, t, e, d, bits=b))
        _, us = timed(f, x, ef, efd)
        nb = 4 * B * n * 2 + 4 * n + 4 * B + 4 * n + 4 * B * n + 4 * n
        _kernel_row(f"qsync_fused_int{bits}", us, B * n, nb, stream,
                    path=path, bits=bits)


def bench_fused_vs_composed(fast=False):
    from repro.comm import IntQuant
    from repro.dist import collectives

    grid = (2, 4)
    dim = 32 if fast else 64
    shapes = [(dim, dim), (dim,), (dim, 2 * dim), (2 * dim,),
              (2 * dim, dim), (dim,), (dim, 2), (2,)]
    key = jax.random.key(0)
    tree = {}
    for i, s in enumerate(shapes):
        key, k = jax.random.split(key)
        tree[f"l{i}"] = jax.random.normal(k, grid + s, jnp.float32)
    ef = jax.tree.map(jnp.zeros_like, tree)
    efd = {k: jnp.zeros(v.shape[2:], v.dtype) for k, v in tree.items()}
    w = jnp.full(grid, 1.0 / (grid[0] * grid[1]))
    codec = IntQuant(bits=8)

    def sync(fused):
        return jax.jit(lambda t, e, d: collectives.coded_sync(
            t, w, codec, ef=e, ef_down=d, fused=fused))

    # interleaved median-of-3 (the bench_agents trick): the 2-core CI clock
    # drifts enough that back-to-back one-shot timings swing ±40%
    comp, fus = sync(False), sync(True)
    cs, fs = [], []
    for _ in range(3):
        _, us = timed(comp, tree, ef, efd, iters=10)
        cs.append(us)
        _, us = timed(fus, tree, ef, efd, iters=10)
        fs.append(us)
    us_c, us_f = sorted(cs)[1], sorted(fs)[1]
    sites_c = _count_round_sites(sync(False), tree, ef, efd)
    sites_f = _count_round_sites(sync(True), tree, ef, efd)
    n_leaves = len(tree)
    emit("roofline_fused_vs_composed", us_f,
         f"speedup={us_c / us_f:.2f};fused_quant_sites={sites_f};"
         f"composed_quant_sites={sites_c};n_leaves={n_leaves}",
         speedup=round(us_c / us_f, 3),
         fused_quant_sites=sites_f,
         composed_quant_sites=sites_c,
         n_leaves=n_leaves,
         composed_us=round(us_c, 1),
         backend=jax.default_backend())


# --------------------------------------------------- dry-run post-processing

def active_param_count(arch: str) -> tuple[int, int]:
    """(total params N, active params N_active) for the GENERATOR."""
    cfg = get_config(arch)
    params = jax.eval_shape(Backbone(cfg).init, jax.random.key(0))
    total = sum(l.size for l in jax.tree_util.tree_leaves(params))
    if cfg.num_experts:
        # expert weights: stacked (layers, E, ...) under blocks/mlp/experts
        def expert_size(tree, path=""):
            total = 0
            if isinstance(tree, dict):
                for k, v in tree.items():
                    total += expert_size(v, path + "/" + k)
                return total
            return tree.size if "/experts/" in path + "/" else 0
        e_total = expert_size(params)
        active = total - e_total + e_total * cfg.experts_per_token // cfg.num_experts
        return total, active
    return total, total


def model_flops_per_step(arch: str, shape_rec: dict) -> float:
    """6·N_active·tokens for train; 2·N_active·tokens for inference."""
    _, n_active = active_param_count(arch)
    meta = shape_rec.get("meta", {})
    kind = meta.get("kind", "train")
    if kind == "train":
        tokens = meta.get("agents", 16) * meta.get("per_agent_batch", 16) * 4096
        return 6.0 * n_active * tokens
    if kind == "prefill":
        from repro.models.config import SHAPES
        s = SHAPES[shape_rec["shape"]]
        return 2.0 * n_active * s.seq_len * s.global_batch
    # decode: one token per sequence
    from repro.models.config import SHAPES
    s = SHAPES[shape_rec["shape"]]
    return 2.0 * n_active * s.global_batch


def bench_dryrun(results_dir="results/dryrun", tag="baseline"):
    rows = sorted(glob.glob(os.path.join(results_dir, f"{tag}__*.json")))
    chips = {"16x16": 256, "2x16x16": 512}
    for path in rows:
        rec = json.load(open(path))
        name = f"roofline_{rec['arch']}_{rec['shape']}_{rec.get('mesh','16x16')}"
        if rec.get("status") == "skipped":
            emit(name, 0.0, f"SKIP:{rec.get('reason','')}")
            continue
        if rec.get("status") != "ok":
            emit(name, 0.0, f"ERROR:{rec.get('error','')[:80]}")
            continue
        r = rec["roofline_per_step"]
        n_chips = chips.get(rec.get("mesh", "16x16"), 256)
        mf = model_flops_per_step(rec["arch"], rec)
        hlo_flops_fleet = rec["flops"] / rec.get("steps_per_call", 1) * n_chips
        useful = mf / hlo_flops_fleet if hlo_flops_fleet else 0.0
        hbm = rec["memory"]["total_hbm_bytes"] / 2 ** 30
        emit(name, 0.0,
             f"compute_s={r['compute_s']:.3e};memory_s={r['memory_s']:.3e};"
             f"collective_s={r['collective_s']:.3e};dominant={r['dominant']};"
             f"useful_flops_ratio={useful:.2f};hbm_GiB_per_dev={hbm:.2f}")


def main(results_dir="results/dryrun", tag="baseline", fast=False):
    bench_kernel_rooflines(fast=fast)
    bench_fused_vs_composed(fast=fast)
    bench_dryrun(results_dir=results_dir, tag=tag)


if __name__ == "__main__":
    main()
