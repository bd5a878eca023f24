"""Bring-up check: the FedGAN training path on TPU, through its entry points.

    python chip_smoke.py            # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4  # four chips: the cross-chip sync only

One chip.  Every phase builds a ``RunSpec`` with device-resident data and
runs it through ``RoundDriver`` (``FedGAN.round_from_data``, donated state):

  (a) the paper's mixed-Gaussian experiment for a few rounds, with its eval
      hook;
  (b) mamba2-2.7b at its published widths (d_model 2560, 80 SSD heads of
      64, state 128), depth cut to 2 of 64 layers, 2 agents stacked on the
      chip, K=5, per-agent batch 2 x 512 tokens, plain FedAvg sync;
  (c) the same round with an int8 wire and error feedback, which runs the
      fused qsync Pallas kernel.  The residuals add three parameter-sized
      copies, so the vocabulary is cut to one chip's share of a deployment
      that splits the embedding and LM head over 2 chips (25,140 of 50,280
      rows); every width stays published.  Then the qsync kernel is
      compared with its f32 jnp reference on the phase's largest parameter
      leaf, and the qpack kernels with theirs.

Four chips.  ``launch.steps.build_train_round`` with the ``agents-data``
plan on a ("data", "model") = (4, 1) mesh: one mamba2 agent (phase (b)
config) per chip, so the sync is a cross-chip all-reduce.  It is compared
with the same round under ``local_only`` followed by a plain jnp mean of
the agents' parameters on one chip.

Each phase prints one line; the last line of stdout is one JSON object
naming the device.  Any failed check raises, and without a TPU the script
exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "mamba2-2.7b"
K = 5
ROUNDS = 2
AGENTS = 2
BATCH, SEQ = 2, 512
VOCAB_SHARE = 2           # chips that split the vocabulary in phase (c)
GiB = 2 ** 30
# lowering to StableHLO and the XLA compile; tracing is left out because
# its events nest (each inner jit reports its own trace inside the outer)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends lowering and compiling inside the block."""

    def __enter__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._add)
        return self

    def _add(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._add)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def agent_spread(params) -> float:
    """Largest |agent i - agent 0| over every parameter: 0 after a sync."""
    return max(float(jnp.max(jnp.abs(x - x[:1, :1])))
               for x in jax.tree_util.tree_leaves(params))


def run_spec(name, spec):
    """Run ``spec`` through its RoundDriver; check losses and the sync."""
    driver, state = spec.round_driver()
    with CompileClock() as clock:
        t0 = time.perf_counter()
        res = driver.run(jax.random.key(spec.seed + 1), state=state)
        wall = time.perf_counter() - t0
    losses = [h[k] for h in res.history for k in ("d_loss", "g_loss")]
    check(len(res.history) == spec.n_rounds, f"{name}: rounds missing")
    check(all(math.isfinite(v) for v in losses), f"{name}: losses {losses}")
    spread = agent_spread(res.state["params"])
    check(spread == 0.0, f"{name}: agents differ by {spread} after the sync")
    line = (f"phase={name} compile_s={clock.seconds:.1f} "
            f"rounds={spec.n_rounds} steps={spec.n_rounds * spec.K} "
            f"wall_s={wall:.1f} d_loss={losses[-2]:.4f} "
            f"g_loss={losses[-1]:.4f} agent_spread={spread}")
    return driver, res, line


def memory_line(compiled) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    # the peak is the process's since start-up, so phase (c) reports the
    # larger of (b) and (c); bytes_in_use is what this phase still holds
    return (f"round_program_GiB={used / GiB:.2f} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


def phase_a():
    from repro.launch.train import experiment_spec
    spec, _ = experiment_spec("mixed_gaussian", K=20, steps=80, eval_every=2,
                              data_mode="device", log_every=0)
    _, res, line = run_spec("a_mixed_gaussian", spec)
    check(len(res.evals) == 2, f"evals {res.evals}")
    scores = {k: v for k, v in res.evals[-1].items()
              if k not in ("round", "step")}
    check(all(math.isfinite(float(v)) for v in scores.values()),
          f"eval scores {scores}")
    print(line, "eval=" + ",".join(f"{k}:{float(v):.4g}"
                                   for k, v in scores.items()), flush=True)


def mamba_spec(**kw):
    from repro.configs import get_config
    from repro.launch.train import arch_smoke_spec
    cfg = get_config(ARCH).scaled(num_layers=2, **kw.pop("cut", {}))
    return arch_smoke_spec(ARCH, cfg=cfg, steps=ROUNDS * K, K=K, seed=0,
                           agents=AGENTS, batch_size=BATCH, seq_len=SEQ,
                           data_mode="device", log_every=0, **kw)


def phase_b():
    spec = mamba_spec()
    driver, res, line = run_spec("b_mamba2_fedavg", spec)
    compiled = driver.compile_chunk(res.state, jax.random.key(spec.seed + 1))
    print(line, memory_line(compiled), flush=True)


def step_bound(scales, block):
    """Per-element bound from per-block quantization steps."""
    return jnp.repeat(scales.astype(jnp.float32), block, axis=-1)


def qsync_vs_ref(state, weights):
    """The fused qsync kernel against the plain f32 jnp reference on the
    round's largest parameter leaf, with its real EF residuals.

    Tolerance: Mosaic and XLA:TPU may round a quotient or a sum differently
    in the last bit, which decides a rounding tie the other way and moves a
    code by one quantization step.  So each element may differ by at most
    one step of its block: the uplink step for the agent residual, and one
    downlink step plus the weighted uplink steps for the average and the
    server residual.  At most 1% of elements may differ at all: ties are
    rare, and an arithmetic error would move most of them."""
    from repro.kernels.qpack.ref import quant_blocks_ref
    from repro.kernels.qsync import kernel, ref
    leaves = jax.tree_util.tree_leaves_with_path(state["params"])
    path, x = max(leaves, key=lambda kv: kv[1].size)
    get = lambda tree: next(v for p, v in
                            jax.tree_util.tree_leaves_with_path(tree)
                            if p == path)
    B = int(weights.size)
    x = x.reshape(B, -1)
    e = get(state["ef"]).reshape(B, -1)
    ed = get(state["ef_down"]).reshape(-1)
    qmax, block = 127, 128
    got = kernel.qsync_flat(weights, x, e, ed, qmax=qmax, block=block)
    want = jax.jit(ref.qsync_flat_ref, static_argnames=("qmax", "block"))(
        weights, x, e, ed, qmax=qmax, block=block)
    _, s_up = quant_blocks_ref(x + e, qmax=qmax, block=block)
    _, s_down = quant_blocks_ref((want[0] + want[2])[None], qmax=qmax,
                                 block=block)
    up = step_bound(s_up, block)
    down = step_bound(s_down, block)[0] + jnp.sum(
        weights.reshape(-1, 1) * up, axis=0)
    stats = {}
    for name, g, w, bound in zip(("synced", "ef", "ef_down"), got, want,
                                 (down, up, down)):
        diff = jnp.abs(g - w)
        stats[name] = (float(jnp.max(diff - bound)),
                       float(jnp.mean((diff > 0).astype(jnp.float32))))
    print(f"phase=c qsync_vs_ref[{jax.tree_util.keystr(path)} {x.shape}]",
          " ".join(f"{k}_differ={frac:.2e} {k}_over_step={over:.3g}"
                   for k, (over, frac) in stats.items()),
          "tol=one_step,differ<=1e-2", flush=True)
    for name, (over, frac) in stats.items():
        check(over <= 0.0, f"qsync {name}: exceeds one step by {over}")
        check(frac <= 0.01, f"qsync {name}: {frac:.2%} of elements differ")


def qpack_vs_ref():
    """qpack kernels against their reference: the nibble pack is integer
    work and must match bit for bit, and dequantizing the same codes and
    scales multiplies exact values.  Quantize may move a code by one
    step at a rounding tie (see ``qsync_vs_ref``)."""
    from repro.kernels.qpack import kernel, ref
    x = 3.0 * jax.random.normal(jax.random.key(7), (AGENTS, 1 << 22))
    q, s = kernel.quant_flat(x, qmax=7)
    q_r, s_r = ref.quant_blocks_ref(x, qmax=7, block=128)
    check(int(jnp.max(jnp.abs(q.astype(jnp.int32) - q_r))) <= 1,
          "quant codes off by more than one step")
    check(bool(jnp.all(s == s_r)), "quant scales differ")
    check(bool(jnp.all(kernel.dequant_flat(q_r, s_r)
                       == ref.dequant_blocks_ref(q_r, s_r, block=128))),
          "dequant differs")
    p = kernel.pack4_flat(q_r)
    check(bool(jnp.all(p == ref.pack4_ref(q_r))), "pack4 differs")
    check(bool(jnp.all(kernel.unpack4_flat(p) == q_r)), "unpack4 differs")
    frac = float(jnp.mean((q != q_r).astype(jnp.float32)))
    return f"qpack_vs_ref: codes_differ={frac:.2e} pack4,unpack4,dequant=exact"


def phase_c():
    from repro.comm import IntQuant
    from repro.configs import get_config
    from repro.core.strategies import FedAvgSync
    vocab = -(-get_config(ARCH).vocab_size // VOCAB_SHARE)
    spec = mamba_spec(cut={"vocab_size": vocab},
                      strategy=FedAvgSync(codec=IntQuant(bits=8)))
    driver, res, line = run_spec("c_mamba2_int8_ef", spec)
    compiled = driver.compile_chunk(res.state, jax.random.key(spec.seed + 1))
    kernels = sum("tpu_custom_call" in op and "qsync_flat" in op
                  for op in compiled.as_text().splitlines())
    check(kernels > 0, "the coded round holds no qsync kernel")
    print(line, f"vocab={vocab} round_qsync_kernels={kernels}",
          memory_line(compiled), flush=True)
    qsync_vs_ref(res.state, driver.fed._w())
    print("phase=c", qpack_vs_ref(), flush=True)


def four_chips():
    """The agents-data round across 4 chips against local_only + a mean."""
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.core.strategies import LocalOnly
    from repro.data import synthetic
    from repro.launch.steps import (AGENTS_DATA, build_train_round,
                                    round_donation)
    from repro.models.config import ShapeConfig
    n = 4
    mesh = jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n])
    cfg = get_config(ARCH).scaled(num_layers=2)
    shape = ShapeConfig("chip_smoke", SEQ, n * BATCH, "train")
    rng = jax.random.key(3)
    tokens = jnp.stack([synthetic.sample_agent_tokens(
        jax.random.fold_in(rng, k), BATCH, SEQ, cfg.vocab_size, agent=i,
        num_agents=n) for k in range(K) for i in range(n)])
    tokens = tokens.reshape(K, 1, n, BATCH, SEQ)
    seeds = jax.random.randint(jax.random.fold_in(rng, 99), (K, 1, n), 0,
                               2 ** 31 - 1).astype(jnp.uint32)
    outs = {}
    with jax.set_mesh(mesh):
        for name, strategy in (("fedavg", None), ("local_only", LocalOnly())):
            built = build_train_round(cfg, shape, mesh, plan=AGENTS_DATA,
                                      K=K, strategy=strategy)
            state_sh, batch_sh, seed_sh = built.in_shardings
            state = jax.jit(built.init, out_shardings=state_sh)(
                jax.random.key(0))
            step = jax.jit(built.fn, in_shardings=built.in_shardings,
                           out_shardings=built.out_shardings,
                           donate_argnums=round_donation(built))
            with CompileClock() as clock:
                t0 = time.perf_counter()
                new, metrics = step(state,
                                    jax.device_put({"tokens": tokens},
                                                   batch_sh),
                                    jax.device_put(seeds, seed_sh))
                jax.block_until_ready(new)
                wall = time.perf_counter() - t0
            losses = [float(jnp.mean(metrics[k])) for k in ("d_loss",
                                                            "g_loss")]
            check(all(map(math.isfinite, losses)), f"{name}: {losses}")
            outs[name] = new
            print(f"phase=4chip_{name} compile_s={clock.seconds:.1f} "
                  f"rounds=1 steps={K} wall_s={wall:.1f} "
                  f"d_loss={losses[0]:.4f} g_loss={losses[1]:.4f}",
                  flush=True)
    synced, local = outs["fedavg"]["params"], outs["local_only"]["params"]
    placed = {d for x in jax.tree_util.tree_leaves(synced)
              for d in x.sharding.device_set}
    check(len(placed) == n, f"params live on {len(placed)} devices")
    spread = agent_spread(synced)
    check(spread == 0.0, f"agents differ by {spread} after the sync")
    # eq. (2)+(3) without the collective: gather each leaf's agents onto
    # one chip and average them there.  Tolerance: both rounds run the same
    # local steps, but in two compiled programs whose fusions may round the
    # last bit differently, and the all-reduce sums in its own order; both
    # stay near f32 resolution, while a wrong or missing sync is off by the
    # agents' whole disagreement.
    dev0 = jax.devices()[0]
    worst = 0.0
    for s, l in zip(jax.tree_util.tree_leaves(synced),
                    jax.tree_util.tree_leaves(local)):
        mean = jnp.mean(jax.device_put(l, dev0), axis=(0, 1))
        gap = float(jnp.max(jnp.abs(jax.device_put(s[0, 0], dev0) - mean)))
        worst = max(worst, gap / (float(jnp.max(jnp.abs(mean))) + 1e-30))
    disagree = agent_spread(local)
    print(f"phase=4chip_compare devices={len(placed)} agent_spread={spread} "
          f"local_only_spread={disagree:.3e} "
          f"sync_vs_mean_rel_gap={worst:.3e} (tol 1e-5)", flush=True)
    check(disagree > 0.0, "local_only agents did not diverge")
    check(worst <= 1e-5, f"sync vs local_only mean: relative gap {worst}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} TPU chips; JAX "
                 f"found {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile_cache={enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        phase_a()
        phase_b()
        phase_c()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
