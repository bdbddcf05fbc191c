"""Plain reference for a dense decoder with grouped-query attention.

Written from the published equations of the Qwen2 / Qwen3 decoder layer
(Hugging Face ``modeling_qwen2.py`` / ``modeling_qwen3.py``), in
``jax.numpy`` and float32 at full matmul precision, with no cache and no
batching:

    h   = x + Wo . attn(RoPE(q), RoPE(k), v),    q, k, v from RMSNorm(x)
          q = Wq h + bq, k = Wk h + bk, v = Wv h + bv   (bias: Qwen2)
          q, k <- RMSNorm over the head dimension       (QK-norm: Qwen3)
    out = h + Wdown (silu(Wgate g) * (Wup g)),   g = RMSNorm(h)
    logits = Whead RMSNorm(out)                  (Whead = embedding if tied)

Heads are grouped: query head ``i`` reads key/value head ``i // (H/KV)``.
RoPE rotates the two halves of each head (``rotate_half``) with inverse
frequencies ``theta ** (-2j / d_head)``.

The weights are drawn here from a seed, in the dtype they are served in.
The reference reads them in that dtype and upcasts them to float32; it
imports nothing of the program under test.  Attention and the output head
run in blocks so that a whole 8-layer slice of a 32B model fits next to
its weights on one 16 GB chip.

``gaps`` runs teacher-forced over one prompt and the tokens served for it
and returns, at every served position, the reference's best logit and its
logit for the served token.  ``control_gaps`` does the same for the token
that the same reference, computed with weights rounded to a lower precision,
puts first: the reading that a comparison has to tell apart from a sound
program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512          # query rows per attention block
HEAD_BLOCKS = 8        # slices of the output head


@dataclasses.dataclass(frozen=True)
class Spec:
    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    rows: int            # embedding rows held (vocab padded by the program)
    theta: float
    eps: float
    qkv_bias: bool
    qk_norm: bool
    tied: bool


def spec_from_config(conf: dict, rows: int) -> Spec:
    """``conf`` is a configuration file of ``chipbench/configs``."""
    c, arch = conf["config"], conf["architecture"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Spec(layers=c["num_hidden_layers"], d_model=d, n_heads=h,
                n_kv=c["num_key_value_heads"],
                d_head=c.get("head_dim", d // h),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rows=rows, theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]), qkv_bias=arch["qkv_bias"],
                qk_norm=arch["qk_norm"], tied=c["tie_word_embeddings"])


def init_weights(spec: Spec, key, dtype=jnp.bfloat16) -> dict:
    """Random weights, stacked over layers.  Matrices have fan-in
    variance, so activations and logits have unit scale; norm scales and
    biases are random too, so that a path which skips them is seen."""
    L, d, h, kv = spec.layers, spec.d_model, spec.n_heads, spec.n_kv
    dh, f = spec.d_head, spec.d_ff
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, mean=0.0):
        x = jax.random.normal(next(ks), shape, jnp.float32)
        return (mean + std * x).astype(dtype)

    w = {
        "embed": normal((spec.rows, d), d ** -0.5),
        "attn_norm": normal((L, d), 0.1, 1.0),
        "wq": normal((L, d, h, dh), d ** -0.5),
        "wk": normal((L, d, kv, dh), d ** -0.5),
        "wv": normal((L, d, kv, dh), d ** -0.5),
        "wo": normal((L, h, dh, d), (h * dh) ** -0.5),
        "mlp_norm": normal((L, d), 0.1, 1.0),
        "w_gate": normal((L, d, f), d ** -0.5),
        "w_up": normal((L, d, f), d ** -0.5),
        "w_down": normal((L, f, d), f ** -0.5),
        "final_norm": normal((d,), 0.1, 1.0),
    }
    if spec.qkv_bias:
        w["bq"] = normal((L, h, dh), 0.5)
        w["bk"] = normal((L, kv, dh), 0.5)
        w["bv"] = normal((L, kv, dh), 0.5)
    if spec.qk_norm:
        w["q_norm"] = normal((L, dh), 0.1, 1.0)
        w["k_norm"] = normal((L, dh), 0.1, 1.0)
    if not spec.tied:
        w["head"] = normal((d, spec.rows), d ** -0.5)
    return w


# -- rounding to a lower precision, for the control ----------------------------
def _quantize(x, kind: str, axis):
    """Round ``x`` to int8 or fp8 (e4m3) with one scale per output channel
    (the max over ``axis``, the contracted axes) and return it dequantized
    in its own dtype."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=axis, keepdims=True)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(x32 / s), -127, 127)
    elif kind == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        q = (x32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        raise ValueError(f"unknown control precision {kind!r}")
    return (q * s).astype(x.dtype)


# contracted (input) axes of each matrix of a layer
IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
           "w_gate": (0,), "w_up": (0,), "w_down": (0,)}


# -- the forward pass -----------------------------------------------------------
def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (T, heads, d_head), position t at row t."""
    t, dh = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, dtype):
    """Causal GQA over the whole sequence, in blocks of query rows.
    q: (T, H, Dh); k, v: (T, KV, Dh)."""
    t, h, dh = q.shape
    kv = k.shape[1]
    nb = -(-t // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - t), (0, 0), (0, 0)))
    qb = qp.reshape(nb, Q_BLOCK, kv, h // kv, dh)
    key_pos = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        q_pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(q_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("kgqs,skd->qkgd", p, v,
                          preferred_element_type=jnp.float32).astype(dtype)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, h, dh)[:t]


def _layer(spec: Spec, dtype, x, lw, kind=None):
    if kind is not None:
        lw = dict(lw, **{k: _quantize(lw[k], kind, ax)
                         for k, ax in IN_AXES.items()})
    up = lambda a: a.astype(dtype)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    h = _rmsnorm(x, lw["attn_norm"], spec.eps)
    q = mm("td,dhk->thk", h, up(lw["wq"])).astype(dtype)
    k = mm("td,dhk->thk", h, up(lw["wk"])).astype(dtype)
    v = mm("td,dhk->thk", h, up(lw["wv"])).astype(dtype)
    if spec.qkv_bias:
        q, k, v = q + up(lw["bq"]), k + up(lw["bk"]), v + up(lw["bv"])
    if spec.qk_norm:
        q = _rmsnorm(q, lw["q_norm"], spec.eps)
        k = _rmsnorm(k, lw["k_norm"], spec.eps)
    q, k = _rope(q, spec.theta), _rope(k, spec.theta)
    a = _attention(q, k, v, dtype)
    x = x + mm("thk,hkd->td", a, up(lw["wo"])).astype(dtype)
    g = _rmsnorm(x, lw["mlp_norm"], spec.eps)
    act = (jax.nn.silu(mm("td,df->tf", g, up(lw["w_gate"])))
           * mm("td,df->tf", g, up(lw["w_up"]))).astype(dtype)
    return x + mm("tf,fd->td", act, up(lw["w_down"])).astype(dtype)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down", "bq", "bk", "bv", "q_norm", "k_norm")


def _hidden(spec: Spec, w: dict, tokens, dtype, kind=None):
    """Final-normed hidden states at every position of ``tokens``, with
    every matrix rounded to ``kind`` (None: as held) as it is used."""
    x = w["embed"][tokens]
    if kind is not None:
        x = _quantize(x, kind, (1,))
    x = x.astype(dtype)
    stack = {k: w[k] for k in LAYER_KEYS if k in w}
    x, _ = jax.lax.scan(
        lambda x, lw: (_layer(spec, dtype, x, lw, kind), None), x, stack)
    return _rmsnorm(x, w["final_norm"], spec.eps)


def logits(spec: Spec, w: dict, tokens, dtype):
    """(T, vocab) logits at every position of ``tokens``, the full
    forward pass in ``dtype``."""
    head = (w["embed"].T if spec.tied else w["head"])[:, :spec.vocab]
    return _hidden(spec, w, tokens, dtype) @ head.astype(dtype)


def uncounted_params(spec: Spec) -> int:
    """The final norm and QK-norm, which ``ModelConfig.param_count()``
    leaves out."""
    return spec.d_model + (2 * spec.layers * spec.d_head if spec.qk_norm
                           else 0)


def _head_slices(spec: Spec, w: dict):
    """(start, stop) of the head's column blocks over the true vocabulary."""
    size = -(-spec.vocab // HEAD_BLOCKS)
    return [(s, min(s + size, spec.vocab)) for s in range(0, spec.vocab, size)]


def _head_block(spec: Spec, w: dict, lo: int, hi: int, kind=None):
    """(d_model, hi - lo) block of the output head."""
    block = w["embed"][lo:hi].T if spec.tied else w["head"][:, lo:hi]
    return block if kind is None else _quantize(block, kind, (0,))


def _gaps(spec: Spec, w: dict, tokens, served, start: int):
    with jax.default_matmul_precision("highest"):
        h = _hidden(spec, w, tokens, jnp.float32)[start:start + served.shape[0]]
        best = jnp.full(served.shape, -jnp.inf, jnp.float32)
        at = jnp.zeros(served.shape, jnp.float32)
        for lo, hi in _head_slices(spec, w):
            z = h @ _head_block(spec, w, lo, hi).astype(jnp.float32)
            best = jnp.maximum(best, z.max(-1))
            inside = (served >= lo) & (served < hi)
            zi = jnp.take_along_axis(z, jnp.clip(served - lo, 0, hi - lo - 1)
                                     [:, None], -1)[:, 0]
            at = jnp.where(inside, zi, at)
    return best, at


@functools.partial(jax.jit, static_argnums=(0, 4))
def gaps(spec: Spec, w: dict, tokens, served, start: int):
    """tokens: (T,) the prompt and every served token but the last;
    served: (n,) the tokens served at positions ``start .. start + n - 1``
    (the prompt's last position and on).  Returns the reference's best
    logit and its logit for each served token, both (n,)."""
    return _gaps(spec, w, tokens, served, start)




@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def control_gaps(spec: Spec, w: dict, tokens, served, start: int, kind: str):
    """The control: at each served position, the float32 reference's best
    logit and its logit for the token that the reference computed in bf16
    with every matrix rounded to ``kind`` puts first, both (n,)."""
    n = served.shape[0]
    with jax.default_matmul_precision("highest"):
        h = _hidden(spec, w, tokens, jnp.float32)[start:start + n]
    hq = _hidden(spec, w, tokens, jnp.bfloat16, kind)[start:start + n]
    best = jnp.full((n,), -jnp.inf, jnp.float32)
    q_best = jnp.full((n,), -jnp.inf, jnp.float32)
    at = jnp.zeros((n,), jnp.float32)
    for lo, hi in _head_slices(spec, w):
        with jax.default_matmul_precision("highest"):
            z = h @ _head_block(spec, w, lo, hi).astype(jnp.float32)
        zq = (hq @ _head_block(spec, w, lo, hi, kind)).astype(jnp.float32)
        best = jnp.maximum(best, z.max(-1))
        j = jnp.argmax(zq, -1)
        m = jnp.take_along_axis(zq, j[:, None], -1)[:, 0]
        take = m > q_best          # ties keep the lower index, like argmax
        at = jnp.where(take, jnp.take_along_axis(z, j[:, None], -1)[:, 0], at)
        q_best = jnp.where(take, m, q_best)
    return best, at
