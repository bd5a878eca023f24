"""End-to-end federated adversarial training of an assigned backbone.

Four agents with non-iid token streams train (G = reduced assigned arch,
D = feature discriminator) under FedGAN; the script reports per-round
losses, the §3.2 communication accounting, and final agent synchrony.

Run:  PYTHONPATH=src python examples/federated_backbone.py \
          --arch mamba2-2.7b --steps 60 --K 5
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.core import FedGAN, FedGANConfig, get_strategy, strategies
from repro.data import FederatedRounds, synthetic
from repro.launch.steps import make_lm_gan_task
from repro.optim import Adam, constant, equal_timescale

tmap = jax.tree_util.tree_map


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-2.7b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--K", type=int, default=5)
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--strategy", default="fedgan",
                    choices=sorted(strategies.STRATEGIES))
    ap.add_argument("--intra-interval", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0,
                    help="run the published widths at this depth "
                         "(0 = the CPU-size smoke config)")
    args = ap.parse_args()

    cfg = (get_config(args.arch).scaled(num_layers=args.layers)
           if args.layers else get_config(args.arch).smoke())
    B, K, T = args.agents, args.K, 32
    strat_kw = ({"intra_interval": args.intra_interval}
                if args.strategy == "hierarchical" else {})
    strategy = get_strategy(args.strategy, **strat_kw)
    task = make_lm_gan_task(cfg)
    fed = FedGAN(task, FedGANConfig(agent_grid=(1, B), sync_interval=K,
                                    strategy=strategy),
                 opt_g=Adam(), opt_d=Adam(),
                 scales=equal_timescale(constant(1e-3)))
    state = fed.init_state(jax.random.key(0))

    rng = jax.random.key(1)
    agent_data = []
    for i in range(B):
        d = {"tokens": synthetic.sample_agent_tokens(
            rng, 512, T, cfg.vocab_size, agent=i, num_agents=B)}
        if cfg.family == "audio":
            d["frames"] = 0.1 * jax.random.normal(
                jax.random.fold_in(rng, 50 + i),
                (512, cfg.encoder_seq, cfg.d_model))
        agent_data.append(d)
    rounds = FederatedRounds(agent_data, (1, B), batch_size=8, sync_interval=K)

    acct = fed.comm_bytes_per_round(state)
    size = f"{cfg.num_layers} layers" if args.layers else "smoke"
    print(f"arch={cfg.name} ({size}) B={B} K={K} strategy={strategy.name}")
    print(f"§3.2 accounting: M={acct['param_bytes_M']/1e6:.1f}MB/agent, "
          f"fedgan {acct['per_agent_per_round']['fedgan']/1e6:.1f}MB/round vs "
          f"distributed {acct['per_agent_per_round']['distributed']/1e6:.1f}MB/round "
          f"(x{acct['ratio']} saving); this strategy moves "
          f"{acct['strategy_bytes_per_round']/1e6:.1f}MB/round")

    round_fn = jax.jit(fed.round)
    for r in range(args.steps // K):
        rng, rb = jax.random.split(rng)
        batches, seeds = rounds.round_batches(rb)
        state, m = round_fn(state, batches, seeds)
        print(f"  round {r:3d} step {(r+1)*K:4d}: "
              f"d_loss={float(jnp.mean(m['d_loss'])):.4f} "
              f"g_loss={float(jnp.mean(m['g_loss'])):.4f} "
              f"lm={float(jnp.mean(m['lm'])):.4f}")

    leaf = jax.tree_util.tree_leaves(state["params"]["gen"])[0]
    synced = bool(jnp.allclose(leaf[0, 0], leaf[0, -1], atol=1e-5))
    # subsampled/adaptive_k legitimately leave agents apart after a round
    # (non-participants keep local state; skip rounds don't sync at all)
    always_syncs = args.strategy not in ("local_only", "subsampled",
                                         "adaptive_k")
    print(f"agents synced after final round: {synced} "
          f"(expected {always_syncs})")


if __name__ == "__main__":
    main()
