"""Layer 1: jaxpr-level trace auditor over the *built* round functions.

A :class:`TracedFn` names one jit target (fn + example args + its
donation contract).  ``audit_traced`` traces it with ``jax.make_jaxpr``
and walks every sub-jaxpr:

* ``host-callback-in-scan`` — callback primitives (``io_callback``,
  ``pure_callback``, ``debug_callback``, ``debug_print``) inside a
  ``scan``/``while`` body: each trip blocks the K-step round on the host,
  serializing exactly the dispatch pipeline the K-scan exists to keep full.
* ``raw-fold-in`` — ``jax.random.key``/``PRNGKey`` *creation*
  (``random_seed``) inside a loop body: the legacy raw-uint32 shim pattern
  (``fold_in(key(0), seed)`` per step) has birthday-collision risk across
  the fleet; keys must be split outside and threaded through the carry.
* ``pad-reuse`` — two ``fold_in`` calls on the same key with the same
  literal salt in one jaxpr: in ``masked_sync`` that is one-time-pad reuse
  (two payloads XORed with the same pad reveal their difference).
* ``donation-miss`` — declared round-state args not covered by
  ``donate_argnums``: the round then keeps two copies of the state live
  (checked at the metadata level because CPU jit ignores donation, so
  alias bytes cannot be measured here).

Findings anchor at the traceback the primitive was bound from
(``eqn.source_info``), so ``# analysis: allow(rule)`` comments work.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable

from repro.analysis.findings import Finding, filter_suppressed
from repro.analysis.lint import repo_root_from_package

LOOP_PRIMS = ("scan", "while")
CALLBACK_PRIMS = ("io_callback", "pure_callback", "debug_callback",
                  "debug_print")

HOST_CALLBACK_RULE = "host-callback-in-scan"
RAW_FOLD_IN_RULE = "raw-fold-in"
PAD_REUSE_RULE = "pad-reuse"
DONATION_RULE = "donation-miss"


@dataclasses.dataclass
class TracedFn:
    """One audit target: a jit-able fn, example (abstract ok) args, and the
    donation contract of its production jit site."""

    name: str
    fn: Callable
    args: tuple
    donate_argnums: tuple = ()
    state_argnums: tuple = ()     # args that are round state (donation candidates)
    origin: tuple = ("", 0)       # (file, line) anchoring metadata-level findings

    def resolved_origin(self, root: str) -> tuple:
        if self.origin[0]:
            return self.origin
        code = getattr(self.fn, "__code__", None) or getattr(
            getattr(self.fn, "__func__", None), "__code__", None)
        if code is not None:
            return _relpath_in(code.co_filename, root), code.co_firstlineno
        return "", 0


def _relpath_in(path: str, root: str) -> str:
    try:
        rel = os.path.relpath(os.path.abspath(path), root)
    except ValueError:
        return ""
    return "" if rel.startswith("..") else rel.replace(os.sep, "/")


def _src_of(eqn, root: str) -> tuple:
    """(repo-relative file, line) of the user frame that bound this eqn."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return "", 0
    return _relpath_in(frame.file_name, root), frame.start_line


def _subjaxprs(params: dict):
    """Every sub-jaxpr hiding in an eqn's params (scan/while/cond/pjit/...)."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr                  # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                        # raw Jaxpr


def _tree_bytes(tree) -> int:
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            n = 1
            for d in leaf.shape:
                n *= d
            total += n * leaf.dtype.itemsize
        except (AttributeError, TypeError):
            pass
    return total


def audit_traced(target: TracedFn, root: str | None = None) -> list:
    """Trace ``target`` and return raw findings (suppressions NOT applied —
    callers go through :func:`run_trace`)."""
    import jax

    root = root or repo_root_from_package()
    findings: list = []

    # --- metadata-level: donation contract -----------------------------
    missing = [i for i in target.state_argnums
               if i not in tuple(target.donate_argnums)]
    if missing:
        ofile, oline = target.resolved_origin(root)
        for i in missing:
            size = _tree_bytes(target.args[i]) if i < len(target.args) else 0
            findings.append(Finding(
                rule=DONATION_RULE, file=ofile, line=oline,
                message=f"[{target.name}] round-state arg {i} "
                        f"({size} bytes here, O(model) at scale) is not in "
                        f"donate_argnums={tuple(target.donate_argnums)} — the "
                        "jitted round keeps two copies of the state live"))

    # --- jaxpr-level rules ---------------------------------------------
    jaxpr = jax.make_jaxpr(target.fn)(*target.args)

    def walk(jx, in_loop: bool):
        fold_ins: dict = {}
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if in_loop and (prim in CALLBACK_PRIMS or "callback" in prim):
                f, l = _src_of(eqn, root)
                findings.append(Finding(
                    rule=HOST_CALLBACK_RULE, file=f, line=l,
                    message=f"[{target.name}] host callback '{prim}' inside "
                            "the K-scan body — every trip blocks the round "
                            "on a host round-trip"))
            if in_loop and prim == "random_seed":
                f, l = _src_of(eqn, root)
                findings.append(Finding(
                    rule=RAW_FOLD_IN_RULE, file=f, line=l,
                    message=f"[{target.name}] PRNG key created from a raw "
                            "seed inside the loop body (the legacy uint32 "
                            "shim pattern) — split keys outside the scan and "
                            "thread them through the carry"))
            if prim == "random_fold_in" and len(eqn.invars) >= 2:
                key_var, salt = eqn.invars[0], eqn.invars[1]
                lit = getattr(salt, "val", None)   # Literal salt only
                scalar = lit is not None and getattr(lit, "ndim", 0) == 0
                if scalar:
                    sig = (id(key_var), repr(lit))
                    if sig in fold_ins:
                        f, l = _src_of(eqn, root)
                        findings.append(Finding(
                            rule=PAD_REUSE_RULE, file=f, line=l,
                            message=f"[{target.name}] fold_in on the same "
                                    f"key with the same literal salt "
                                    f"({lit!r}) twice in one computation — "
                                    "pad/key reuse (first use at "
                                    f"{fold_ins[sig][0]}:{fold_ins[sig][1]})"))
                    else:
                        fold_ins[sig] = _src_of(eqn, root)
            for sub in _subjaxprs(eqn.params):
                walk(sub, in_loop or prim in LOOP_PRIMS)

    walk(jaxpr.jaxpr, in_loop=False)
    return findings


def audit_built(built, *, donate_argnums: tuple = (), root: str | None = None,
                name: str | None = None) -> list:
    """Audit a ``repro.launch.steps.BuiltStep`` (the dryrun integration).
    Traces ``built.fn`` on its ShapeDtypeStruct inputs — mesh-free, so it
    runs on one device even for production-mesh builds."""
    kind = built.meta.get("kind", "step")
    target = TracedFn(
        name=name or f"built.{kind}",
        fn=built.fn, args=tuple(built.input_sds),
        donate_argnums=tuple(donate_argnums),
        state_argnums=(0,) if kind == "train" else ())
    return audit_traced(target, root)


# ---------------------------------------------------------------------------
# Default targets: the toy rounds the CLI audits on every run
# ---------------------------------------------------------------------------


def default_targets() -> list:
    """Three single-device round targets covering the stream path, the
    device-resident sampling path, and the secure-sum sync path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FedGAN, FedGANConfig, make_gan_task
    from repro.core.strategies import FedAvgSync
    from repro.data import DeviceFederatedData
    from repro.models.gan_nets import Toy2DDiscriminator, Toy2DGenerator
    from repro.optim import Adam, constant, equal_timescale
    from repro.privacy import SecureAgg

    K, A, b = 4, 3, 8
    task = make_gan_task(Toy2DGenerator(theta0=0.5), Toy2DDiscriminator(psi0=0.5))

    def build(strategy=None):
        return FedGAN(task,
                      FedGANConfig(agent_grid=(1, A), sync_interval=K,
                                   strategy=strategy),
                      opt_g=Adam(), opt_d=Adam(),
                      scales=equal_timescale(constant(1e-3)))

    fed = build()
    state = jax.eval_shape(fed.init_state, jax.random.key(0))
    batches = {"x": jax.ShapeDtypeStruct((K, 1, A, b), jnp.float32),
               "z": jax.ShapeDtypeStruct((K, 1, A, b), jnp.float32)}
    keys = jax.random.split(jax.random.key(0), K * A).reshape(K, 1, A)

    # donation contract: repro.run.RoundDriver._jit donates argnums=0
    targets = [TracedFn("round.stream", fed.round, (state, batches, keys),
                        donate_argnums=(0,), state_argnums=(0,))]

    fed_secure = build(FedAvgSync(secure_agg=SecureAgg(seed=0)))
    targets.append(TracedFn("round.secure", fed_secure.round,
                            (state, batches, keys),
                            donate_argnums=(0,), state_argnums=(0,)))

    agent_data = [{"x": np.zeros((32,), np.float32)} for _ in range(A)]
    data = DeviceFederatedData.from_agent_data(
        agent_data, (1, A), b,
        sample_extra=lambda r, s: {"z": jax.random.uniform(r, s, minval=-1,
                                                           maxval=1)})
    targets.append(TracedFn(
        "round.device",
        lambda st, key: fed.round_from_data(st, data, key),
        (state, jax.random.key(1)),
        donate_argnums=(0,), state_argnums=(0,)))
    return targets


def run_trace(root: str | None = None, targets=None) -> list:
    """Audit the default (or given) targets; suppressions applied."""
    root = root or repo_root_from_package()
    targets = default_targets() if targets is None else targets
    findings: list = []
    for t in targets:
        findings.extend(audit_traced(t, root))
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return filter_suppressed(findings, root)
