"""Batched serving driver: prefill a batch of prompts, then decode with
the KV cache (greedy), on any assigned architecture (smoke preset on CPU;
the full configs serve via the same code path on the production mesh).

The fixed-batch compile-once prefill shape here is also the template for
the federated service's inference endpoint (``launch.service.
InferenceEndpoint``): the CNN is single-shot, so its endpoint is "prefill
only" — one jitted step at a fixed batch size, requests padded to it.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --batch 4 --prompt-len 64 --gen 32

Federated classifiers serve through the same fixed-batch contract via
``--classifier`` (a model registry name) plus ``--task`` (the registry
task that fixes the input geometry):

  PYTHONPATH=src python -m repro.launch.serve --classifier transformer \
      --task cifar --batch 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import synthetic_tokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.transformer import count_params, init_params


def serve(arch: str, batch: int, prompt_len: int, gen: int,
          smoke: bool = True, log=print):
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    log(f"arch={arch} params={count_params(params)/1e6:.2f}M "
        f"batch={batch} prompt={prompt_len} gen={gen}")

    total = prompt_len + gen
    prefill = jax.jit(make_prefill_step(cfg, total))
    decode = jax.jit(make_decode_step(cfg))

    prompts = synthetic_tokens(jax.random.PRNGKey(1), batch, prompt_len,
                               cfg.vocab_size)
    extra = {}
    if cfg.embed_input:
        raise SystemExit(f"{arch}: serve demo uses token archs; "
                         "vlm/audio serve via the same decode_step with "
                         "stub embeddings (see dryrun decode shapes)")
    if cfg.cross_attention:
        extra["enc_out"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.encoder_seq, cfg.d_model)).astype(cfg.param_dtype)

    t0 = time.time()
    logits_last, cache = prefill(params, {"tokens": prompts, **extra})
    jax.block_until_ready(logits_last)
    t_prefill = time.time() - t0
    nxt = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)

    outs = [nxt]
    t0 = time.time()
    for _ in range(gen - 1):
        nxt, cache = decode(params, {"tokens": nxt[:, None],
                                     "cache": cache, **extra})
        outs.append(nxt)
    jax.block_until_ready(nxt)
    t_decode = time.time() - t0
    gen_tokens = jnp.stack(outs, axis=1)
    log(f"prefill: {t_prefill*1e3:.1f} ms "
        f"({batch * prompt_len / max(t_prefill, 1e-9):.0f} tok/s)")
    log(f"decode : {t_decode*1e3:.1f} ms "
        f"({batch * (gen - 1) / max(t_decode, 1e-9):.1f} tok/s)")
    log(f"sample continuation (seq 0): {gen_tokens[0, :12].tolist()}")
    return gen_tokens


def serve_classifier(model_name: str, task_name: str, batch: int,
                     requests: int = 40, log=print):
    """Single-shot classifier serving: build the registry model at the
    task's geometry and drive the federated inference endpoint (the
    prefill-only analogue of the decode loop above — one compiled shape,
    requests padded to it)."""
    from repro.data.pipeline import parse_task
    from repro.launch.service import InferenceEndpoint
    from repro.models.registry import build_model

    task = parse_task(task_name)
    model = build_model(model_name, task.input_shape, task.num_classes)
    params = model.init(jax.random.PRNGKey(0))
    n_par = sum(p.size for p in jax.tree.leaves(params))
    log(f"classifier={model_name} task={task.name} "
        f"input={task.input_shape} classes={task.num_classes} "
        f"params={n_par/1e3:.1f}K batch={batch}")

    endpoint = InferenceEndpoint(model.apply, batch,
                                 input_shape=task.input_shape)
    x, _ = task.data(jax.random.PRNGKey(1), requests)
    endpoint.submit(x)
    t0 = time.time()
    preds = endpoint.flush(params)
    t_serve = time.time() - t0
    log(f"served {preds.shape[0]} requests in {endpoint.batches} "
        f"batches: {t_serve*1e3:.1f} ms "
        f"({preds.shape[0] / max(t_serve, 1e-9):.0f} req/s)")
    log(f"sample predictions: {preds[:12].tolist()}")
    return preds


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--classifier", default=None,
                    help="serve a federated classifier from the model "
                         "registry instead of a token arch")
    ap.add_argument("--task", default="digits",
                    help="registry task fixing the classifier's input "
                         "geometry (with --classifier)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-smoke) config")
    args = ap.parse_args()
    enable_compile_cache()
    if args.classifier is not None:
        serve_classifier(args.classifier, args.task, args.batch)
        return
    serve(args.arch, args.batch, args.prompt_len, args.gen,
          smoke=not args.full)


if __name__ == "__main__":
    main()
