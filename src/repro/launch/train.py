"""Training entry point.

Two modes:

* ``--mode paper``: the letter's own experiment — federated CNN training
  over simulated wireless devices with any of fl/fd/fld/mixfld/mix2fld.

* ``--mode lm``: Mix2FLD at LM scale on the local mesh — pods (simulated
  as vmapped pod-param stacks on CPU; real pod axis on TPU) run local SGD
  steps with the KD-regularised loss, sync via the FD uplink + output-to-
  model conversion + FL downlink (launch.steps), training one of the
  assigned architectures (reduced preset by default).

Usage:
  PYTHONPATH=src python -m repro.launch.train --mode paper --protocol mix2fld
  PYTHONPATH=src python -m repro.launch.train --mode lm --arch qwen2-0.5b \
      --preset 25m --steps 50
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro.channel import ChannelConfig
from repro.configs import get_config
from repro.core.protocols import FederatedConfig, FederatedTrainer
from repro.data import synthetic_tokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (make_favg_step, make_fd_sync_step,
                                make_local_train_step)
from repro.models.cnn import CNN
from repro.models.transformer import count_params, init_params


def run_paper(args):
    from benchmarks.common import protocol_dataset
    dev = protocol_dataset(num_devices=args.devices, iid=not args.noniid)
    ch = ChannelConfig(num_devices=args.devices,
                       p_up_dbm=40.0 if args.symmetric else 23.0)
    fc = FederatedConfig(protocol=args.protocol, num_devices=args.devices,
                         local_iters=args.local_iters, local_batch=32,
                         server_iters=args.local_iters,
                         max_rounds=args.rounds)
    h = FederatedTrainer(CNN(), fc, ch).run(*dev, log=print)
    print(f"final acc={h['acc'][-1]:.3f} "
          f"converged_round={h['converged_round']} "
          f"cum_time={h['cum_time_s'][-1]:.1f}s")
    return h


def _preset(cfg, preset: str):
    if preset == "full":
        return cfg
    if preset == "100m":
        return dataclasses.replace(
            cfg, num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
            head_dim=64, d_ff=2048, vocab_size=32000, param_dtype="float32",
            fd_buckets=64, max_position=4096)
    # 25m: CPU-friendly end-to-end demo
    return dataclasses.replace(
        cfg, num_layers=8, d_model=512, num_heads=8, num_kv_heads=4,
        head_dim=64, d_ff=1536, vocab_size=8192, param_dtype="float32",
        fd_buckets=64, max_position=2048,
        num_experts=min(cfg.num_experts, 8) if cfg.is_moe else 0,
        num_shared_experts=min(cfg.num_shared_experts, 1),
        top_k=min(cfg.top_k, 2) if cfg.is_moe else 0,
        moe_d_ff=1536 if cfg.is_moe else 0)


def run_lm(args):
    cfg = _preset(get_config(args.arch), args.preset)
    n_pods = args.pods
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    print(f"arch={args.arch} preset={args.preset} "
          f"params={count_params(params)/1e6:.1f}M pods={n_pods}")

    pod_params = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_pods,) + p.shape), params)
    # the server's own model state (Alg. 1: w_s persists across rounds);
    # kept pod-stacked-but-consistent so conversion runs pod-locally
    server_stack = pod_params
    local_step = jax.jit(make_local_train_step(cfg, n_pods))
    favg_step = jax.jit(jax.vmap(make_favg_step(cfg)))
    fd_sync = jax.jit(make_fd_sync_step(cfg, n_pods,
                                        ks_iters=args.ks_iters))

    B, S = args.batch, args.seq
    data = synthetic_tokens(jax.random.fold_in(key, 1),
                            n_pods * B * 8, S + 1, cfg.vocab_size)
    data = data.reshape(n_pods, B * 8, S + 1)
    seed_batch = {"tokens": data[0, :B, :]}  # inverse-mixed seeds stand-in
    gout = jnp.full((cfg.fd_buckets, cfg.fd_buckets), 1.0 / cfg.fd_buckets)

    t0 = time.time()
    for step in range(args.steps):
        k = jax.random.fold_in(key, 100 + step)
        idx = jax.random.randint(k, (n_pods, B), 0, data.shape[1])
        batch_tokens = jnp.take_along_axis(
            data, idx[..., None], axis=1)[..., :S]
        batch = {"tokens": batch_tokens,
                 "gout": jnp.broadcast_to(gout, (n_pods,) + gout.shape)}
        pod_params, metrics = local_step(pod_params, batch)
        if (step + 1) % args.sync_every == 0:
            # Mix2FLD sync: thin uplink (per-pod favg), pod-local server
            # conversion from the consistent w_s, replicated-compute
            # downlink (devices replace their params with G_mod)
            favg = favg_step(pod_params, {"tokens": batch_tokens})
            server_stack, gout = fd_sync(server_stack, favg, seed_batch)
            pod_params = server_stack
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(jnp.mean(metrics["loss"]))
            print(f"step {step:4d} loss={loss:.4f} "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps,
                  jax.tree.map(lambda p: p[0], pod_params))
        print(f"checkpoint -> {args.ckpt_dir}")
    return pod_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("paper", "lm"), default="paper")
    # paper mode
    ap.add_argument("--protocol", default="mix2fld")
    ap.add_argument("--devices", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--local-iters", type=int, default=150)
    ap.add_argument("--noniid", action="store_true")
    ap.add_argument("--symmetric", action="store_true")
    # lm mode
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--preset", choices=("25m", "100m", "full"),
                    default="25m")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync-every", type=int, default=10)
    ap.add_argument("--ks-iters", type=int, default=4)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "paper":
        run_paper(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
