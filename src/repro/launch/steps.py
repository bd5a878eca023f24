"""Step builders: (arch x shape x mesh x plan) -> jittable fn + shardings +
ShapeDtypeStruct input specs.

Three step kinds map to the assigned input shapes:
  train   — FedGAN round: K local adversarial steps + sync (train_4k)
  prefill — generator forward + decode-cache build, last-token logits
  decode  — ONE new token against a seq_len KV/SSM cache

Mesh plans for training:
  agents-data      (baseline, the paper's mapping): one agent per
                   (pod, data) index; tensor parallel over "model" within
                   each agent; sync = all-reduce over ("pod","data").
  agents-pod-fsdp  (beyond-paper memory optimisation): agents = pods only,
                   weights additionally sharded over "data" (FSDP) inside
                   each agent — for the >10B-param archs whose per-agent
                   TP-16 shard exceeds v5e HBM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.fedgan import FedGAN, FedGANConfig, GANTask
from repro.core.strategies import strategy_from_mode
from repro.dist.sharding import (batch_axes, filter_spec, named_shardings,
                                 param_specs, shape_of)
from repro.launch.mesh import mesh_dims
from repro.models.adversarial import AdversarialLM
from repro.models.config import ArchConfig, ShapeConfig
from repro.models.transformer import Backbone
from repro.optim import Adam, constant, equal_timescale

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# Mesh plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    name: str
    agent_lead: tuple          # mesh axes carrying the (P, A) agent grid
    fsdp_axis: str | None      # extra weight-sharding axis inside an agent
    act_batch_axes: tuple      # axes for per-agent activation batch dims
    dp_over_model: bool = False  # intra-agent DP: batch over "model", FSDP weights

    def agent_grid(self, mesh) -> tuple[int, int]:
        dims = mesh_dims(mesh)
        if self.name == "agents-pod-fsdp":
            return (dims.get("pod", 1), 1)
        return (dims.get("pod", 1), dims["data"])

    def specs(self, tree, mesh):
        from repro.dist.sharding import dp_param_specs
        if self.dp_over_model:
            return dp_param_specs(tree, mesh, lead=self.agent_lead)
        return param_specs(tree, mesh, lead=self.agent_lead,
                           fsdp_axis=self.fsdp_axis)


# Baseline (the paper's mapping): one agent per (pod, data) index, tensor
# parallel over "model" within each agent.
AGENTS_DATA = MeshPlan("agents-data", ("pod", "data"), None, ())
# Beyond-paper: intra-agent DATA parallelism over the model axis — per-agent
# batch sharded 16-ways, weights FSDP-stored over "model", gathered at use.
AGENTS_DATA_DP = MeshPlan("agents-data-dp", ("pod", "data"), None, ("model",),
                          dp_over_model=True)
# Beyond-paper: agents = pods only; weights FSDP over "data" (for >10B archs
# whose per-agent TP-16 shard exceeds HBM).
AGENTS_POD_FSDP = MeshPlan("agents-pod-fsdp", ("pod",), "data", ("data",))
SERVING = MeshPlan("serving", (), None, ("pod", "data"))

PLANS = {p.name: p for p in (AGENTS_DATA, AGENTS_DATA_DP, AGENTS_POD_FSDP,
                             SERVING)}


# ---------------------------------------------------------------------------
# LM adversarial task (fused grads: single G forward)
# ---------------------------------------------------------------------------


def make_lm_gan_task(cfg: ArchConfig, *, adv_weight: float = 0.1) -> GANTask:
    model = AdversarialLM(cfg, adv_weight=adv_weight)

    def fused(params, batch, rng):
        tokens = batch["tokens"]
        frames = batch.get("frames")
        gen, disc = params["gen"], params["disc"]

        def gfwd(gp):
            out = model.generator.apply(gp, tokens, encoder_frames=frames)
            return out["hidden"], out["logits"], out["aux"]

        (h, logits, aux), g_vjp = jax.vjp(gfwd, gen)
        real = jax.lax.stop_gradient(model.real_features(gen, tokens))
        h_sg = jax.lax.stop_gradient(h)

        def dloss(dp):
            lr_ = model.discriminator.apply(dp, real)
            lf_ = model.discriminator.apply(dp, h_sg)
            return (jnp.mean(jax.nn.softplus(-lr_))
                    + jnp.mean(jax.nn.softplus(lf_)))

        ld, gd = jax.value_and_grad(dloss)(disc)

        def gobj(h_, logits_):
            adv = jnp.mean(jax.nn.softplus(
                -model.discriminator.apply(disc, h_)))
            lm = model.lm_loss(logits_, tokens)
            return lm + model.adv_weight * adv, (lm, adv)

        (lg, (lm, adv)), (dh, dlogits) = jax.value_and_grad(
            gobj, argnums=(0, 1), has_aux=True)(h, logits)
        gg = g_vjp((dh, dlogits,
                    jnp.asarray(cfg.router_aux_weight, jnp.float32)))[0]
        return gd, gg, {"d_loss": ld, "g_loss": lg, "lm": lm, "adv": adv,
                        "aux": aux}

    def disc_loss(params, batch, rng):
        fake, _, _ = model.fake_features(params["gen"], batch["tokens"],
                                         batch.get("frames"))
        real = model.real_features(params["gen"], batch["tokens"])
        return model.disc_loss(params["disc"], real, fake)

    def gen_loss(params, batch, rng):
        total, _ = model.gen_loss(params["gen"], params["disc"],
                                  batch["tokens"], batch.get("frames"))
        return total

    return GANTask(init=model.init, disc_loss=disc_loss, gen_loss=gen_loss,
                   fused_grads=fused)


# ---------------------------------------------------------------------------
# Cache sharding
# ---------------------------------------------------------------------------


def cache_specs(cache_sds, mesh, *, batch: int):
    """PartitionSpec tree for a decode cache.

    k/v: (...stack, B, S, nkv, hd) — shard B over ("pod","data") when
    divisible, otherwise shard S over "data" (context parallelism for the
    batch-1 long-decode); heads over "model" when divisible, else head_dim.
    ssm: (...stack, B, nh, hd, ds) — heads over "model".
    conv: (...stack, B, k, ch) — channels over "model".
    """
    dims = mesh_dims(mesh)
    bdiv = dims.get("pod", 1) * dims["data"]
    batch_ok = batch % bdiv == 0

    def leaf_spec(path_key, leaf):
        nd = leaf.ndim
        ent = [None] * nd
        if path_key in ("k", "v"):
            b_dim, s_dim, h_dim, d_dim = nd - 4, nd - 3, nd - 2, nd - 1
            if batch_ok:
                ent[b_dim] = ("pod", "data")
            else:
                ent[s_dim] = "data"
            if leaf.shape[h_dim] % dims["model"] == 0:
                ent[h_dim] = "model"
            elif leaf.shape[d_dim] % dims["model"] == 0:
                ent[d_dim] = "model"
        elif path_key == "ssm":
            b_dim, h_dim = nd - 4, nd - 3
            if batch_ok:
                ent[b_dim] = ("pod", "data")
            if leaf.shape[h_dim] % dims["model"] == 0:
                ent[h_dim] = "model"
        elif path_key.startswith("conv"):
            b_dim, c_dim = nd - 3, nd - 1
            if batch_ok:
                ent[b_dim] = ("pod", "data")
            if path_key == "conv_x" and leaf.shape[c_dim] % dims["model"] == 0:
                ent[c_dim] = "model"
        # pos and anything else: replicated
        return filter_spec(mesh, tuple(ent), leaf.shape)

    def walk(tree, key=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = type(tree)
            return t(walk(v, key) for v in tree)
        return leaf_spec(key, tree)

    return walk(cache_sds)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BuiltStep:
    fn: Callable                  # jit-able, positional args
    input_sds: tuple              # ShapeDtypeStruct pytree per arg
    in_shardings: tuple
    out_shardings: Any
    meta: dict
    init: Callable | None = None  # train rounds: rng -> initial state


def round_donation(built: "BuiltStep") -> tuple:
    """``donate_argnums`` for jitting a BuiltStep.  Train rounds return the
    new state as output 0, so arg 0 (the old state) is donatable — without
    it the jitted round holds TWO copies of params+opt live (the PR 7
    dryrun finding: memory_analysis showed zero alias bytes).  Serving
    steps return fresh outputs and donate nothing."""
    return (0,) if built.meta.get("kind") == "train" else ()


def _token_sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def build_train_round(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                      plan: MeshPlan = AGENTS_DATA, K: int = 20,
                      strategy=None, mode: str = "fedgan", sync_dtype=None,
                      intra_interval: int = 0,
                      adv_weight: float = 0.1) -> BuiltStep:
    """The FedGAN round for the LM adversarial task on this mesh.  Pass a
    ``repro.core.strategies.SyncStrategy`` as ``strategy``; the legacy
    ``mode``/``sync_dtype``/``intra_interval`` trio resolves to one."""
    Pn, A = plan.agent_grid(mesh)
    B_agents = Pn * A
    if shape.global_batch % B_agents:
        raise ValueError(f"global_batch {shape.global_batch} % {B_agents} agents")
    per_agent = shape.global_batch // B_agents

    if strategy is None:
        strategy = strategy_from_mode(mode, intra_interval=intra_interval,
                                      sync_dtype=sync_dtype)
    task = make_lm_gan_task(cfg, adv_weight=adv_weight)
    fed = FedGAN(task,
                 FedGANConfig(agent_grid=(Pn, A), sync_interval=K,
                              strategy=strategy),
                 opt_g=Adam(), opt_d=Adam(),
                 scales=equal_timescale(constant(1e-4)))

    state_sds = jax.eval_shape(fed.init_state, jax.random.key(0))
    state_specs = {
        "params": plan.specs(state_sds["params"], mesh),
        "opt_g": plan.specs(state_sds["opt_g"], mesh),
        "opt_d": plan.specs(state_sds["opt_d"], mesh),
        "step": P(),
    }
    # strategy-carried entries (e.g. repro.comm error feedback): anything
    # agent-stacked — every leaf leading with the (P, A) grid, like the EF
    # uplink residuals — shards exactly like the params; shared per-leaf
    # state (the downlink residual) has no agent lead and is replicated
    for k, sds in state_sds.items():
        if k in state_specs:
            continue
        leaves = jax.tree_util.tree_leaves(sds)
        stacked = bool(leaves) and all(l.shape[:2] == (Pn, A)
                                       for l in leaves)
        state_specs[k] = (plan.specs(sds, mesh) if stacked
                          else tmap(lambda _: P(), sds))

    batch = {"tokens": _token_sds((K, Pn, A, per_agent, shape.seq_len))}
    batch_specs = {"tokens": filter_spec(
        mesh, (None, "pod", "data", plan.act_batch_axes or None, None),
        batch["tokens"].shape)}
    if cfg.family == "audio":
        batch["frames"] = jax.ShapeDtypeStruct(
            (K, Pn, A, per_agent, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        batch_specs["frames"] = filter_spec(
            mesh, (None, "pod", "data", plan.act_batch_axes or None, None, None),
            batch["frames"].shape)
    seeds = _token_sds((K, Pn, A), jnp.uint32)
    seeds_spec = filter_spec(mesh, (None, "pod", "data"), seeds.shape)

    def round_fn(state, batches, seeds):
        with batch_axes(*plan.act_batch_axes):
            return fed.round(state, batches, seeds)

    in_shardings = (named_shardings(mesh, state_specs),
                    named_shardings(mesh, batch_specs),
                    named_shardings(mesh, seeds_spec))
    out_shardings = (named_shardings(mesh, state_specs), None)

    return BuiltStep(
        fn=round_fn,
        input_sds=(state_sds, batch, seeds),
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        meta={"kind": "train", "plan": plan.name, "K": K,
              "mode": strategy.name,
              "agents": B_agents, "per_agent_batch": per_agent,
              "state_specs": state_specs},
        init=fed.init_state,
    )


def build_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                  fsdp: bool = False) -> BuiltStep:
    bb = Backbone(cfg)
    dims = mesh_dims(mesh)
    B = shape.global_batch

    pspecs = param_specs(
        jax.eval_shape(bb.init, jax.random.key(0)), mesh,
        fsdp_axis="data" if fsdp else None)

    tokens = _token_sds((B, shape.seq_len))
    tok_spec = filter_spec(mesh, (("pod", "data"), None), tokens.shape)
    args_sds = [jax.eval_shape(bb.init, jax.random.key(0)), tokens]
    arg_specs = [pspecs, tok_spec]
    if cfg.family == "audio":
        frames = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        args_sds.append(frames)
        arg_specs.append(filter_spec(mesh, (("pod", "data"), None, None),
                                      frames.shape))

    def prefill_fn(params, tokens, frames=None):
        out = bb.prefill(params, tokens, encoder_frames=frames,
                         logits_mode="last")
        return {"logits": out["logits"], "cache": out["cache"]}

    return BuiltStep(
        fn=prefill_fn,
        input_sds=tuple(args_sds),
        in_shardings=tuple(named_shardings(mesh, s) for s in arg_specs),
        out_shardings=None,
        meta={"kind": "prefill", "plan": "serving", "fsdp": fsdp},
    )


def build_decode(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                 ring_cache: bool = False, fsdp: bool = False) -> BuiltStep:
    bb = Backbone(cfg, ring_cache=ring_cache)
    B = shape.global_batch
    S = shape.seq_len

    params_sds = jax.eval_shape(bb.init, jax.random.key(0))
    pspecs = param_specs(params_sds, mesh, fsdp_axis="data" if fsdp else None)
    cache_sds = jax.eval_shape(lambda: bb.init_cache(B, S))
    cspecs = cache_specs(cache_sds, mesh, batch=B)

    token = _token_sds((B, 1))
    tok_spec = filter_spec(mesh, (("pod", "data"), None), token.shape)
    index = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_fn(params, token, cache, index):
        return bb.decode(params, token, cache, index)

    return BuiltStep(
        fn=decode_fn,
        input_sds=(params_sds, token, cache_sds, index),
        in_shardings=(named_shardings(mesh, pspecs),
                      named_shardings(mesh, tok_spec),
                      named_shardings(mesh, cspecs),
                      None),
        out_shardings=None,
        meta={"kind": "decode", "plan": "serving", "ring": ring_cache,
              "fsdp": fsdp, "cache_seq": S},
    )


def build_step(cfg: ArchConfig, shape: ShapeConfig, mesh, **kw) -> BuiltStep:
    if shape.kind == "train":
        return build_train_round(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh, **kw)
    if shape.kind == "decode":
        ring = kw.pop("ring_cache", cfg.sliding_window > 0 and
                      shape.name == "long_500k")
        return build_decode(cfg, shape, mesh, ring_cache=ring, **kw)
    raise ValueError(shape.kind)


def input_specs(arch_cfg: ArchConfig, shape: ShapeConfig, mesh, **kw):
    """The deliverable-(e) entry point: ShapeDtypeStruct stand-ins for every
    model input of the (arch x shape) step on this mesh."""
    return build_step(arch_cfg, shape, mesh, **kw).input_sds
