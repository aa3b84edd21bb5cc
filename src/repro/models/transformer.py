"""Architecture assembly: init, train/prefill forward, single-token decode.

One ``Transformer`` facade covers all six assigned families (dense, moe,
ssm, hybrid, vlm, audio).  Layers are **scanned** (stacked params, leading
layer axis) with rematerialisation, so HLO size and compile time are
depth-independent and activation memory is O(1) in depth.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import kvcache
from .attention import (cross_attention, encode_cross_kv, gqa_attention,
                        mla_attention)
from .layers import (apply_norm, dense_init, dtype_of, embed_init,
                     grad_dtype_guard, init_norm)
from .mamba2 import init_mamba, mamba2_forward
from .mlp import init_mlp, mlp
from .moe import init_moe, moe_ffn
from .shardhooks import constrain

# Minimal-memory remat: each scanned layer saves only its input; the whole
# layer recomputes in backward.  (dots_with_no_batch_dims_saveable was
# measured to save ~10 activation tensors per layer at 1M-token batches —
# see EXPERIMENTS.md §Perf iteration log.)
REMAT_POLICY = jax.checkpoint_policies.nothing_saveable


def _opt_barrier(x):
    return jax.lax.optimization_barrier(x)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(cfg, key):
    """One transformer block (dense / moe / audio flavours)."""
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg, cfg.d_model), "ln2": init_norm(cfg, cfg.d_model)}
    p["attn"] = attn_mod.init_attn(cfg, ks[0])
    if cfg.is_moe:
        p["moe"] = init_moe(cfg, ks[1])
    else:
        p["mlp"] = init_mlp(cfg, ks[1])
    if cfg.cross_attention:
        p["ln_x"] = init_norm(cfg, cfg.d_model)
    return p


def _stack(init_fn, cfg, key, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(cfg, k))(keys)


def init_params(cfg, key):
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    p = {"final_norm": init_norm(cfg, cfg.d_model)}
    if not cfg.embed_input:
        p["embed"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt)
    if not cfg.tie_embeddings or cfg.embed_input:
        p["unembed"] = embed_init(ks[1], cfg.vocab_size, cfg.d_model, dt).T
    if cfg.pos_emb == "learned":
        p["pos_embed"] = embed_init(ks[2], cfg.max_position, cfg.d_model, dt)

    if cfg.family in ("dense", "vlm", "moe", "audio"):
        p["blocks"] = _stack(_init_block, cfg, ks[3], cfg.num_layers)
    elif cfg.family == "ssm":
        p["blocks"] = _stack(
            lambda c, k: {"ln": init_norm(c, c.d_model),
                          "mamba": init_mamba(c, k)},
            cfg, ks[3], cfg.num_layers)
    elif cfg.family == "hybrid":
        G = cfg.num_layers // cfg.attn_every
        A = cfg.attn_every
        flat = _stack(
            lambda c, k: {"ln": init_norm(c, c.d_model),
                          "mamba": init_mamba(c, k)},
            cfg, ks[3], G * A)
        p["blocks"] = jax.tree.map(
            lambda x: x.reshape((G, A) + x.shape[1:]), flat)
        p["shared_attn"] = _init_block(cfg, ks[4])  # one shared block
    else:
        raise ValueError(cfg.family)
    return p


def unembed_matrix(cfg, params):
    if cfg.tie_embeddings and not cfg.embed_input:
        return params["embed"].T
    return params["unembed"]


# ---------------------------------------------------------------------------
# Blocks (functional)
# ---------------------------------------------------------------------------

def _attn_block(cfg, p, x, q_pos, kv_pos, cache, positions3, enc_out,
                enc_kv_cache):
    aux = jnp.zeros((), jnp.float32)
    # barrier: stops XLA hoisting a whole-stack f32 convert of the
    # remat-saved layer inputs out of the backward scan (measured 75 GiB
    # on deepseek train_4k; EXPERIMENTS.md §Perf)
    x = _opt_barrier(x)
    x = grad_dtype_guard(x)  # keep the residual cotangent in bf16
    x = constrain(x, "resid")
    h = apply_norm(cfg, p["ln1"], x)
    if cfg.attn_type == "mla":
        a, new_cache = mla_attention(cfg, p["attn"], h, q_pos, kv_pos, cache)
    else:
        a, new_cache = gqa_attention(cfg, p["attn"], h, q_pos, kv_pos, cache,
                                     positions3)
    x = x + a
    if cfg.cross_attention:
        h = apply_norm(cfg, p["ln_x"], x)
        if enc_kv_cache is not None:
            ekv = enc_kv_cache
        else:
            ekv = encode_cross_kv(cfg, p["attn"], enc_out)
        x = x + cross_attention(cfg, p["attn"], h, ekv)
        if new_cache is not None:
            new_cache = dict(new_cache, xk=ekv["k"], xv=ekv["v"])
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.is_moe:
        y, aux = moe_ffn(cfg, p["moe"], h, constrain=_MOE_CONSTRAIN[0])
    else:
        y = mlp(cfg, p["mlp"], h)
    return x + y, aux, new_cache


def _mamba_block(cfg, p, x, cache):
    x = _opt_barrier(x)
    x = grad_dtype_guard(x)
    x = constrain(x, "resid")
    h = apply_norm(cfg, p["ln"], x)
    y, new_cache = mamba2_forward(cfg, p["mamba"], h, cache)
    return x + y, new_cache


# Hook for launch.sharding to constrain MoE dispatch tensors (set at trace
# time; single-element list so tests can leave it as identity).
_MOE_CONSTRAIN = [None]


def set_moe_constraint(fn):
    _MOE_CONSTRAIN[0] = fn


# ---------------------------------------------------------------------------
# Forward (train / prefill / decode)
# ---------------------------------------------------------------------------

def forward(cfg, params, batch, cache=None, *, remat=True,
            return_hidden=False):
    """Returns (logits, aux_loss, new_cache) — or (hidden, aux, cache)
    when ``return_hidden`` (the chunked loss computes logits itself so the
    full (B,S,V) tensor is never materialised).

    batch keys: "tokens" (B,T) or "embeds" (B,T,D); optional "enc_out"
    (B,Senc,D) for audio.  With ``cache``: decode (T==1) or cache-building
    prefill (T==seq).
    """
    if "embeds" in batch:
        x = batch["embeds"].astype(dtype_of(cfg))
        B, T = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, T = tokens.shape
        x = params["embed"][tokens]

    if cache is not None:
        pos0 = cache["pos"]
        Sc = _cache_slot_len(cfg, cache)
    else:
        pos0 = jnp.zeros((), jnp.int32)
        Sc = T

    q_pos = jnp.broadcast_to(pos0 + jnp.arange(T), (B, T)).astype(jnp.int32)
    kv_pos = None
    if cfg.family != "ssm":
        if cache is not None and T == 1:
            kv_pos = kvcache.kv_positions(cfg, pos0, Sc, B)
        else:
            kv_pos = q_pos  # train / prefill: attention over the live keys
    positions3 = jnp.stack([q_pos] * 3, axis=-1) if cfg.mrope else None

    if cfg.pos_emb == "learned":
        x = x + params["pos_embed"][q_pos[0]][None]

    enc_out = batch.get("enc_out")
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "vlm", "moe", "audio"):
        x, aux_total, new_layer_cache = _scan_attn_blocks(
            cfg, params["blocks"], x, q_pos, kv_pos, cache, positions3,
            enc_out, remat)
    elif cfg.family == "ssm":
        x, new_layer_cache = _scan_mamba_blocks(cfg, params["blocks"], x,
                                                cache, remat)
    elif cfg.family == "hybrid":
        x, new_layer_cache = _scan_hybrid(cfg, params, x, q_pos, kv_pos,
                                          cache, remat)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(cfg, params["final_norm"], constrain(x, "resid"))

    new_cache = None
    if cache is not None:
        new_cache = dict(new_layer_cache)
        new_cache["pos"] = pos0 + T
    if return_hidden:
        return x, aux_total, new_cache
    logits = constrain(x @ unembed_matrix(cfg, params), "logits")
    return logits, aux_total, new_cache


def _cache_slot_len(cfg, cache):
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cache["attn"]["k"].shape[2]
    return cache["layers"]["k" if cfg.attn_type != "mla" else "ckv"].shape[2]


def _maybe_remat(fn, remat):
    return jax.checkpoint(fn, policy=REMAT_POLICY) if remat else fn


def _scan_attn_blocks(cfg, blocks, x, q_pos, kv_pos, cache, positions3,
                      enc_out, remat):
    has_cache = cache is not None
    decode = has_cache and x.shape[1] == 1

    def body(carry, inp):
        x, aux = carry
        if has_cache:
            lp, lc = inp
            enc_kv = {"k": lc["xk"], "v": lc["xv"]} if (
                cfg.cross_attention and decode and enc_out is None) else None
            layer_cache = {k: v for k, v in lc.items()
                           if k not in ("xk", "xv")}
        else:
            lp, layer_cache, enc_kv = inp, None, None
        # stop XLA hoisting a whole-stack dtype convert of the scanned
        # weights out of the loop (CPU lowering converts bf16 operands)
        lp = _opt_barrier(lp)
        y, aux_l, new_lc = _attn_block(cfg, lp, x, q_pos, kv_pos, layer_cache,
                                       positions3, enc_out, enc_kv)
        if has_cache and cfg.cross_attention and "xk" not in new_lc:
            new_lc = dict(new_lc, xk=lc["xk"], xv=lc["xv"])
        return (y, aux + aux_l), new_lc

    body = _maybe_remat(body, remat and not decode)
    xs = (blocks, cache["layers"]) if has_cache else blocks
    (x, aux), new_cache_layers = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), xs)
    out_cache = {"layers": new_cache_layers} if has_cache else None
    return x, aux, out_cache


def _scan_mamba_blocks(cfg, blocks, x, cache, remat):
    has_cache = cache is not None

    def body(x, inp):
        lp, lc = inp if has_cache else (inp, None)
        y, new_lc = _mamba_block(cfg, lp, x, lc)
        return y, new_lc

    body = _maybe_remat(body, remat and not has_cache)
    xs = (blocks, cache["layers"]) if has_cache else blocks
    x, new_layers = jax.lax.scan(body, x, xs)
    return x, ({"layers": new_layers} if has_cache else None)


def _scan_hybrid(cfg, params, x, q_pos, kv_pos, cache, remat):
    """Zamba2: G super-blocks of (attn_every mamba layers + shared attn)."""
    has_cache = cache is not None
    shared = params["shared_attn"]
    decode = has_cache and x.shape[1] == 1

    def inner(x, inp):
        lp, lc = inp if has_cache else (inp, None)
        y, new_lc = _mamba_block(cfg, lp, x, lc)
        return y, new_lc

    def body(x, inp):
        if has_cache:
            mp, mc, ac = inp
            x, new_mc = jax.lax.scan(inner, x, (mp, mc))
        else:
            mp, ac = inp, None
            x, new_mc = jax.lax.scan(inner, x, mp)
        # shared attention block (same weights every super-block)
        y, _, new_ac = _attn_block(cfg, shared, x, q_pos, kv_pos,
                                   ac if has_cache else None, None, None,
                                   None)
        if has_cache:
            return y, (new_mc, new_ac)
        return y, None

    body = _maybe_remat(body, remat and not decode)
    if has_cache:
        xs = (params["blocks"], cache["mamba"], cache["attn"])
        x, (new_m, new_a) = jax.lax.scan(body, x, xs)
        return x, {"mamba": new_m, "attn": new_a}
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x, None


# ---------------------------------------------------------------------------
# Facade + param accounting
# ---------------------------------------------------------------------------

class Transformer:
    """Thin facade bundling config + pure functions."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, key):
        return init_params(self.cfg, key)

    def apply(self, params, batch, cache=None, remat=True):
        return forward(self.cfg, params, batch, cache, remat=remat)

    def init_cache(self, batch_size, seq_len):
        return kvcache.init_cache(self.cfg, batch_size, seq_len)


class TransformerClassifier:
    """Tiny dense transformer as a federated client model: flatten the
    input, cut it into ``seq_len`` patch tokens, project to d_model, run
    the scanned dense stack, mean-pool position logits.

    Same .init/.apply contract as :class:`repro.models.cnn.CNN` (float32
    params, logits (B, num_classes)), so FD-family cohorts can mix it
    with the conv/MLP clients.  Built on the same ``init_params`` /
    ``forward`` stack the serving configs use (``embed_input`` front
    door, learned positions, GELU MLP)."""

    def __init__(self, num_classes: int, input_shape: tuple,
                 d_model: int = 32, num_layers: int = 2, num_heads: int = 2,
                 head_dim: int = 16, d_ff: int = 64, seq_len: int = 16):
        from ..configs import ArchConfig  # local: configs never imports models
        self.num_classes = num_classes
        self.input_shape = tuple(int(s) for s in input_shape)
        total = 1
        for s in self.input_shape:
            total *= s
        if total % seq_len:
            raise ValueError(
                f"input shape {self.input_shape} ({total} features) does "
                f"not split into seq_len={seq_len} patch tokens")
        self.seq_len = seq_len
        self.patch_dim = total // seq_len
        self.cfg = ArchConfig(
            name="fed_transformer", family="dense",
            source="registry classifier (this repo)",
            num_layers=num_layers, d_model=d_model, num_heads=num_heads,
            num_kv_heads=num_heads, d_ff=d_ff, vocab_size=num_classes,
            head_dim=head_dim, attn_type="gqa", pos_emb="learned",
            max_position=seq_len, embed_input=True, mlp_act="gelu",
            param_dtype="float32")

    def init(self, key):
        kp, kt = jax.random.split(key)
        patch = {"w": dense_init(kp, self.patch_dim, self.cfg.d_model,
                                 jnp.float32),
                 "b": jnp.zeros((self.cfg.d_model,), jnp.float32)}
        return {"patch": patch, "tf": init_params(self.cfg, kt)}

    def apply(self, params, x):
        """x: (B, *input_shape) -> logits (B, num_classes)."""
        if tuple(x.shape[1:]) != self.input_shape:
            raise ValueError(
                f"TransformerClassifier built for input shape "
                f"{self.input_shape} but got a batch of shape "
                f"{tuple(x.shape[1:])}")
        toks = x.reshape(x.shape[0], self.seq_len, self.patch_dim)
        h = toks @ params["patch"]["w"] + params["patch"]["b"]
        logits, _, _ = forward(self.cfg, params["tf"], {"embeds": h},
                               remat=False)
        return logits.mean(axis=1)

    def num_params(self, params) -> int:
        return sum(p.size for p in jax.tree.leaves(params))


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_params(cfg, params) -> int:
    """Active parameters per token (MoE: top_k of routed experts)."""
    total = count_params(params)
    if not cfg.is_moe:
        return total

    def routed_size(p):
        return sum(p["blocks"]["moe"][w].size for w in ("w1", "w2", "w3"))

    routed = routed_size(params)
    active_routed = routed * cfg.top_k / cfg.num_experts
    return int(total - routed + active_routed)
