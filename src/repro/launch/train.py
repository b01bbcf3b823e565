"""End-to-end federated training driver (the runnable launcher).

On real hardware this runs the full fed loop on the production mesh; on CPU
it runs reduced configs end-to-end (examples/ and the integration tests use
it that way).

Usage:
  python -m repro.launch.train --arch smollm-135m --reduced --rounds 3 \
      --clients 4 --seq 128 --batch 2

Two workloads (``--workload``):

* ``full`` (default) — every client fine-tunes the whole model and proposes
  full parameters; rounds go through ``fed.distributed.make_fed_round`` (the
  mesh-ready path).
* ``lora`` — clients train low-rank adapters on a frozen base and propose
  only the adapter delta; rounds go through the fused engine on the
  ``(K, D_adapter)`` packed buffer (``repro.fed.api.run``),
  with ``--byzantine`` clients running the update-level attack
  ``--scenario``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.core import AFAConfig
from repro.core.reputation import init_reputation
from repro.data import make_token_stream
from repro.fed.distributed import FedRoundConfig, make_fed_round
from repro.models import build_model
from repro.utils.compile_cache import use_compile_cache


def make_fed_batches(cfg, stream, rng, *, K, S, b, seq):
    toks = []
    for _ in range(K):
        batch = next(iter(stream.batches(rng, batch=S * b, seq=seq, n_batches=1)))
        toks.append(
            {k: v.reshape(S, b, seq) for k, v in batch.items()}
        )
    batch = {
        k: jnp.asarray(np.stack([t[k] for t in toks])) for k in toks[0]
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(K, S, b, cfg.prefix_len, cfg.frontend_dim)).astype(np.float32)
        )
    if cfg.family == "audio":
        batch = {
            "frame_embeds": jnp.asarray(
                rng.normal(size=(K, S, b, seq, cfg.frontend_dim)).astype(np.float32)
            ),
            "labels": batch["labels"],
        }
    return batch


def run_lora(args) -> int:
    """The ``--workload lora`` route: fused-engine federated fine-tuning on
    low-rank adapter proposals (see repro.fed.workload)."""
    from repro.fed.api import run
    from repro.fed.simulator import SimConfig
    from repro.fed.workload import get_workload

    workload = get_workload(
        "lora", arch=args.arch, reduced=args.reduced, rank=args.rank
    )
    sim = SimConfig(
        num_clients=args.clients, bad_frac=args.byzantine / args.clients,
        scenario=args.scenario, rounds=args.rounds,
        local_epochs=args.local_steps, batch_size=args.batch, lr=args.lr,
    )
    t0 = time.perf_counter()
    res = run(workload, sim, seq=args.seq)
    dt = time.perf_counter() - t0
    print(
        f"lora workload: adapter_dim={res['adapter_dim']} "
        f"({100 * res['adapter_fraction']:.2f}% of {res['param_dim']} params)",
        flush=True,
    )
    for rnd, (err, gf) in enumerate(zip(res["test_error"], res["good_frac"])):
        blocked = int(res["blocked"][rnd].sum())
        print(
            f"round {rnd}: test_error={float(err):.4f} good_frac={float(gf):.2f} "
            f"blocked={blocked}",
            flush=True,
        )
    print(f"{args.rounds} rounds in {dt:.1f}s (one fused scan)", flush=True)
    if args.ckpt:
        save_pytree(args.ckpt, {
            "params": res["params"],
            "merged": workload.merged_params(res["params"]),
        })
        print(f"saved {args.ckpt}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--workload", choices=("full", "lora"), default="full",
                    help="full: whole-model proposals through make_fed_round; "
                         "lora: adapter-delta proposals through the fused engine")
    ap.add_argument("--rank", type=int, default=4,
                    help="LoRA rank (lora workload only)")
    ap.add_argument("--scenario", default="byzantine",
                    help="update-level attack for the byzantine clients "
                         "(lora workload only)")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--byzantine", type=int, default=0,
                    help="first N clients behave byzantine: scrambled labels AND "
                         "amplified inputs (paper-style strong faults)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.workload == "lora":
        return run_lora(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().with_(param_dtype="float32", compute_dtype="float32")
    cfg = cfg.with_(fed_clients=args.clients, fed_mode=cfg.fed_mode if not args.reduced else "vmap")
    model = build_model(cfg)

    fr = make_fed_round(
        model,
        FedRoundConfig(
            num_clients=args.clients, local_steps=args.local_steps, lr=args.lr,
            afa=AFAConfig(), mode=cfg.fed_mode,
        ),
    )
    fed_round = jax.jit(fr)

    params = model.init(jax.random.PRNGKey(0))
    rep = init_reputation(args.clients)
    n_k = jnp.ones((args.clients,), jnp.float32)
    stream = make_token_stream(vocab=cfg.vocab_size, n=50_000)
    rng = np.random.default_rng(0)

    eval_batch = make_fed_batches(cfg, stream, rng, K=1, S=1, b=args.batch, seq=args.seq)
    eval_batch = jax.tree_util.tree_map(lambda x: x[0, 0], eval_batch)
    loss_j = jax.jit(lambda p, b: model.loss_fn(p, b)[0])

    for rnd in range(args.rounds):
        batch = make_fed_batches(
            cfg, stream, rng, K=args.clients, S=args.local_steps, b=args.batch, seq=args.seq
        )
        if args.byzantine:
            for k in range(args.byzantine):
                # paper-style byzantine: labels scrambled AND a constant label
                # (mode collapse) — strong, systematic wrong gradient
                bad = np.full(batch["labels"][k].shape, rnd % cfg.vocab_size, np.int32)
                batch["labels"] = batch["labels"].at[k].set(jnp.asarray(bad))
                batch["tokens"] = batch["tokens"].at[k].set(
                    jnp.asarray(np.zeros(batch["tokens"][k].shape, np.int32))
                )
        t0 = time.perf_counter()
        params, rep, metrics = fed_round(params, rep, n_k, batch)
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        dt = time.perf_counter() - t0
        ev = float(loss_j(params, eval_batch))
        print(
            f"round {rnd}: eval_loss={ev:.4f} good_frac={float(metrics['good_frac']):.2f} "
            f"afa_rounds={int(metrics['afa_rounds'])} ({dt:.1f}s)",
            flush=True,
        )
    if args.ckpt:
        save_pytree(args.ckpt, {"params": params, "rep": rep._asdict()})
        print(f"saved {args.ckpt}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
