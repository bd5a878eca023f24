"""The streaming round driver — pillar four's hot loop.

Replaces the seed-era blocking loop (host-assembled round tensors, no
donation, a forced device sync per round for metrics) with:

  * **donated round execution** — ``jax.jit(..., donate_argnums=0)`` on the
    FedGAN state, so the (params, Adam moments) buffers are reused in place
    instead of reallocated every round;
  * **device-resident sampling** — with a ``DeviceFederatedData`` the K
    minibatches are gathered inside the jitted round
    (``FedGAN.round_from_data``), eliminating the K× host→device transfer
    and the per-agent Python assembly loop;
  * **multi-round scan chunking** — for small models (the paper's GANs)
    ``rounds_per_chunk`` rounds run as ONE ``lax.scan`` dispatch, hiding
    per-round dispatch + Python overhead entirely;
  * **non-blocking metrics** — per-round metrics are reduced device-side
    and fetched only at ``log_every`` boundaries (and once at the end);
    no round ever blocks on a host float() just to fill the history;
  * **hooks** — periodic evals on the intermediary's averaged params
    (``repro.run.evals``) and checkpointing, both at round granularity;
  * **host spans** — every line of :meth:`RoundDriver.run` lies in one of
    ``driver.prepare`` (DP check, init, key schedule, the next stream
    batch), ``driver.dispatch`` (the jitted call), ``driver.boundary``
    (log, evals, checkpoints) or ``driver.collect`` (the final wait and
    the history fetch); ``driver.wait`` marks each blocking wait for the
    device inside the last two.  Each is a ``jax.profiler.TraceAnnotation``,
    on the device trace's clock, whose seconds also add to
    ``timings["host_s"]``.

Streaming datasets (too large for device memory) run the same driver
through ``StreamingFederatedData``: double-buffered host assembly +
async ``device_put``, bit-identical trajectories to the legacy loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.data.federated import (DeviceFederatedData, FederatedData,
                                  FederatedRounds, StreamingFederatedData,
                                  round_key_schedule)

tmap = jax.tree_util.tree_map

# the spans that partition ``RoundDriver.run``; ``driver.wait`` nests
# inside ``driver.boundary`` and ``driver.collect``
TOP_SPANS = ("driver.prepare", "driver.dispatch", "driver.boundary",
             "driver.collect")


@dataclasses.dataclass
class RunResult:
    """What a driver run returns.  ``history`` is one dict of float metrics
    per round (same contract as the legacy ``RunSpec.run`` history);
    ``evals`` one dict per eval point; ``timings`` wall-clock accounting:
    ``host_s``, the seconds in each driver span, and ``round_gap_s``, the
    host work per round (the top-level spans less ``driver.wait``); see
    docs/training.md."""

    fed: Any
    state: Any
    history: list
    evals: list
    timings: dict

    def legacy_tuple(self):
        return self.fed, self.state, self.history


def _chunk_sizes(n_rounds: int, per_chunk: int, *cadences: int) -> list[int]:
    """Split ``n_rounds`` into scan chunks of at most ``per_chunk`` that
    never cross a nonzero cadence boundary (evals/checkpoints must observe
    the state at exactly their round)."""
    per_chunk = max(per_chunk, 1)
    sizes, r = [], 0
    while r < n_rounds:
        c = min(per_chunk, n_rounds - r)
        for cad in cadences:
            if cad:
                c = min(c, cad - r % cad)
        sizes.append(c)
        r += c
    return sizes


def _dp_data_shape(data):
    """(batch_size, min per-agent dataset size) of the pipeline, or None
    when the data object does not expose them."""
    if isinstance(data, DeviceFederatedData):
        # one-time setup fetch (before the round loop starts), not per-round
        return data.batch_size, int(np.asarray(data.sizes).min())  # analysis: allow(host-sync)
    rounds = data.rounds if isinstance(data, StreamingFederatedData) else data
    if isinstance(rounds, FederatedRounds):
        n_min = min(jax.tree_util.tree_leaves(d)[0].shape[0]
                    for d in rounds.agent_data)
        return rounds.batch_size, n_min
    return None


def check_dp_sample_rate(dp, data):
    """Refuse an accountant ``sample_rate`` the pipeline does not deliver.

    Every step samples ``batch_size`` examples from each agent's local
    dataset, so the worst-case per-example participation rate is
    ``min(1, batch_size / min_i |R_i|)``.  A configured q below that makes
    :meth:`DPSGD.epsilon` report a spend the mechanism does not achieve —
    a silent privacy accounting failure, so this raises instead of
    warning (mirroring the strategy refusal matrix)."""
    shape = _dp_data_shape(data)
    if shape is None:
        return
    batch_size, n_min = shape
    q_actual = min(1.0, batch_size / max(n_min, 1))
    if dp.sample_rate < q_actual - 1e-9:
        raise ValueError(
            f"DPSGD sample_rate={dp.sample_rate} understates the pipeline's "
            f"participation rate: batch_size={batch_size} from a smallest "
            f"agent dataset of {n_min} examples samples at rate "
            f"{q_actual:.6g} per step, so the accountant's epsilon would "
            "not be delivered — set sample_rate >= batch_size / min |R_i| "
            "(or leave the conservative default of 1.0)")


@dataclasses.dataclass
class RoundDriver:
    """Drives ``n_rounds`` FedGAN rounds over a :class:`FederatedData`.

    ``data`` may be a ``DeviceFederatedData`` (device-resident fast path),
    a ``StreamingFederatedData``, or a bare ``FederatedRounds`` (wrapped
    into a streaming pipeline).  ``eval_hooks`` entries are callables
    ``(fed, state, round_idx) -> dict`` (see ``repro.run.evals``).
    """

    fed: Any
    data: Any
    n_rounds: int
    log_every: int = 1
    eval_every: int = 0
    eval_hooks: Sequence[Callable] = ()
    ckpt_every: int = 0
    ckpt_dir: str = ""
    rounds_per_chunk: int = 1
    donate: bool = True
    verbose: bool = True

    def __post_init__(self):
        if isinstance(self.data, FederatedRounds):
            self.data = StreamingFederatedData(self.data)
        if self.eval_every and not self.eval_hooks:
            raise ValueError("eval_every is set but eval_hooks is empty")
        # memoized jitted executables: repeated .run() calls (resumed or
        # repeated training, benchmarking) must not recompile
        self._round_jit = None
        self._chunk_jit = None

    # ------------------------------------------------------------------
    def run(self, rng, state=None) -> RunResult:
        """Execute the round loop.  ``rng`` seeds the data/step keys (the
        legacy per-round split schedule); ``state`` defaults to a fresh
        init from an independent split of ``rng`` — pass one explicitly to
        continue a run (or to control the init key separately, as the
        RunSpec shim does for legacy parity)."""
        self._host_s = {}
        with self._span("driver.prepare"):
            dp = getattr(self.fed.cfg, "dp", None)
            if dp is not None:
                check_dp_sample_rate(dp, self.data)
            if state is None:
                rng, init_rng = jax.random.split(rng)
                state = self.fed.init_state(init_rng)
            kind = getattr(self.data, "kind", "stream")
            self._evals = []
            t0 = time.perf_counter()
        if kind == "device":
            state, raw = self._run_device(rng, state)
        else:
            state, raw = self._run_stream(rng, state)
        with self._span("driver.collect"):
            with self._span("driver.wait"):
                jax.block_until_ready(
                    (jax.tree_util.tree_leaves(state)[0], raw))
            # one batched fetch AFTER all rounds dispatched — the fix for
            # the eager per-round fetch, not a regression of it
            history = []
            for c, arr in jax.device_get(raw):  # analysis: allow(host-sync)
                # one round's metrics (c None) or a chunk's, stacked
                history += ([arr] if c is None else
                            [tmap(lambda x: x[i], arr) for i in range(c)])
            history = [tmap(float, m) for m in history]
            total = time.perf_counter() - t0
            K = self.fed.cfg.sync_interval
            timings = {
                "total_s": total,
                "steps_per_s": self.n_rounds * K / max(total, 1e-9),
                "data_kind": kind,
            }
            if dp is not None:
                timings["dp_epsilon"] = dp.epsilon(self.n_rounds * K)
        host = self._host_s
        work = (sum(host.get(k, 0.0) for k in TOP_SPANS)
                - host.get("driver.wait", 0.0))
        timings.update(round_gap_s=work / max(self.n_rounds, 1), host_s=host)
        return RunResult(self.fed, state, history, self._evals, timings)

    @contextlib.contextmanager
    def _span(self, name):
        """One host span of :meth:`run`: a ``jax.profiler.TraceAnnotation``
        whose seconds also add to ``timings["host_s"][name]``."""
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._host_s[name] = (self._host_s.get(name, 0.0)
                                  + time.perf_counter() - t)

    # ------------------------------------------------------------------
    def _jit(self, fn):
        return jax.jit(fn, donate_argnums=0) if self.donate else jax.jit(fn)

    def _run_stream(self, rng, state):
        with self._span("driver.prepare"):
            if self._round_jit is None:
                self._round_jit = self._jit(self.fed.round)
            round_fn = self._round_jit
            raw = []          # (None, one round's metrics tree)
            it = self.data.iter_rounds(rng, self.n_rounds)
        for r in range(self.n_rounds):
            with self._span("driver.prepare"):
                batches, seeds = next(it)
            with self._span("driver.dispatch"):
                state, metrics = round_fn(state, batches, seeds)
                # device-side reduction; no host sync on the round path
                raw.append((None, tmap(jnp.mean, metrics)))
            with self._span("driver.boundary"):
                state = self._boundaries(state, r, lambda: raw[r][1])
        return state, raw

    def _device_chunk(self):
        """The jitted multi-round scan the device path dispatches."""
        if self._chunk_jit is None:
            def chunk_fn(st, d, keys):
                def body(st, k):
                    st, m = self.fed.round_from_data(st, d, k)
                    return st, tmap(jnp.mean, m)
                return jax.lax.scan(body, st, keys)

            self._chunk_jit = self._jit(chunk_fn)
        return self._chunk_jit

    def compile_chunk(self, state, rng):
        """Lower and compile the device path's first chunk for ``state`` —
        the executable :meth:`run` dispatches, for reading its HLO or
        memory use.  Device-resident data only."""
        c = _chunk_sizes(self.n_rounds, self.rounds_per_chunk,
                         self.eval_every, self.ckpt_every)[0]
        keys = jnp.stack(round_key_schedule(rng, self.n_rounds))[:c]
        return self._device_chunk().lower(state, self.data, keys).compile()

    def _run_device(self, rng, state):
        with self._span("driver.prepare"):
            data = self.data
            chunk_jit = self._device_chunk()
            raw = []          # (length, stacked metrics tree) per chunk
            r = 0
            keys = jnp.stack(round_key_schedule(rng, self.n_rounds))
        # Per-round metric slicing is deferred to the end of the run:
        # eagerly chaining ops onto the in-flight chunk backs up the
        # dispatch queue and stalls the loop.
        for c in _chunk_sizes(self.n_rounds, self.rounds_per_chunk,
                              self.eval_every, self.ckpt_every):
            with self._span("driver.prepare"):
                chunk_keys = keys[r:r + c]
            with self._span("driver.dispatch"):
                state, metrics = chunk_jit(state, data, chunk_keys)
                raw.append((c, metrics))
            with self._span("driver.boundary"):
                for rr in range(r, r + c):
                    state = self._boundaries(
                        state, rr,
                        lambda rr=rr, m=metrics, base=r: tmap(
                            lambda x: x[rr - base], m))
                r += c
        return state, raw

    # ------------------------------------------------------------------
    def _boundaries(self, state, r, get_metrics):
        """Per-round host work: logging (the only place round metrics are
        fetched mid-run — ``get_metrics`` materializes them on demand),
        periodic evals, periodic checkpoints."""
        K = self.fed.cfg.sync_interval
        last = r == self.n_rounds - 1
        if self.log_every and (r % self.log_every == 0 or last):
            m = get_metrics()
            with self._span("driver.wait"):
                jax.block_until_ready(m)
            m = tmap(float, m)
            if self.verbose:
                d, g = m.get("d_loss"), m.get("g_loss")
                print(f"round {r:5d}/{self.n_rounds} step {(r + 1) * K:6d} "
                      f"d_loss={d:.4f} g_loss={g:.4f}", flush=True)
        if self.eval_every and ((r + 1) % self.eval_every == 0 or last):
            scores = {}
            for hook in self.eval_hooks:
                scores.update(hook(self.fed, state, r))
            dp = getattr(self.fed.cfg, "dp", None)
            if dp is not None:
                # closed-form RDP accountant (host-side, cheap): the privacy
                # spent by the (r+1)*K local steps so far
                scores["dp_epsilon"] = dp.epsilon((r + 1) * K)
            self._evals.append({"round": r, "step": (r + 1) * K, **scores})
            if self.verbose:
                pretty = " ".join(f"{k}={v:.4g}" for k, v in scores.items())
                print(f"eval  round {r} step {(r + 1) * K}: {pretty}",
                      flush=True)
        if self.ckpt_dir and self.ckpt_every and (r + 1) % self.ckpt_every == 0:
            save_checkpoint(self.ckpt_dir, state, step=(r + 1) * K,
                            metadata={"round": r, "K": K})
        return state


def train(fed, data, n_rounds: int, rng, **kwargs) -> RunResult:
    """One-call convenience over :class:`RoundDriver`."""
    return RoundDriver(fed, data, n_rounds, **kwargs).run(rng)
