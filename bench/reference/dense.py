"""Plain float32 reference of a dense decoder-only transformer.

Written from the model's equations, not from the program under test: it
imports nothing of ``repro``.  It reads the weights the benchmark made
(``bench/weights.py``) by name from the parameter tree and the shape of the
model from the configuration file's ``model`` section:

    x_0     = embed[token]
    h       = norm(x)                                  # rmsnorm or layernorm
    x       = x + Wo . attn(rope(Wq h), rope(Wk h), Wv h)  # GQA, causal,
                                                        # sliding window
    x       = x + ffn(norm(x))                         # silu(h Wg) * (h Wu)
                                                        # then Wd; or
                                                        # gelu_tanh(h Win) Wd
    logits  = norm_f(x_L) . E^T                        # E = embed if tied,
                                                        # else lm_head
    H_e     = entropy(softmax(norm_e(x_e) . W_e))      # exit head e, read
                                                        # after layer e

RoPE rotates the two halves of each head (q1, q2) by angles
``pos / theta ** (2i / head_dim)``; attention scores are scaled by
``attention_multiplier`` (1/sqrt(head_dim) where the file gives none).

Everything runs in float32 with ``highest`` matmul precision, one layer's
weights upcast at a time, so a 5 GB bf16 model never needs its 10 GB
float32 copy.  ``quant`` names a lower-precision control, the step a later
change might be tempted to take: under ``"int8"`` every weight matmul
takes int8 weights (scaled per output column) and int8 activations (per
row); under ``"fp8"`` both are float8 e4m3, scaled the same way.

``matmul_params`` and ``attention_flops`` count the model FLOPs of a token
from the same shapes (``bench/metrics/step_mfu.py``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

QUANTS = ("none", "int8", "fp8")


def matmul_params(m: Dict) -> int:
    """Matmul parameters one token uses in the full-depth forward:
    attention and FFN projections of every layer, and the output head (the
    embedding gather and the exit probes excluded)."""
    d, f = m["hidden_size"], m["intermediate_size"]
    nq, nkv, h = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    ffn = (3 if m["hidden_act"] == "silu" else 2) * d * f
    layer = 2 * d * nq * h + 2 * d * nkv * h + ffn
    return m["num_hidden_layers"] * layer + m["vocab_size"] * d


def attention_flops(m: Dict, c) -> float:
    """FLOPs of one token's attention over c positions, every layer: the
    scores q.k and the weighted sum p.v, 2 * Nq * H * c each."""
    return 4.0 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * c


def _q(a, axis, quant):
    """Round trip through int8 or float8 e4m3, scaled so the largest
    magnitude along ``axis`` (per row or per column) maps to the top."""
    top = 127.0 if quant == "int8" else 448.0
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """x [..., K] @ w [K, N] in float32, or in the control's precision."""
    if quant != "none":
        x, w = _q(x, -1, quant), _q(w, 0, quant)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(m, x, p):
    if m["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + m["rms_norm_eps"]) * p["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + m["norm_epsilon"]) * p["scale"] \
        + p["bias"]


def _rope(x, pos, theta):
    """x [T, N, H], pos [T]: rotate halves (x1, x2) by pos * inv_freq."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(m, x):
    if m["hidden_act"] == "silu":
        return x * jax.nn.sigmoid(x)
    c = math.sqrt(2.0 / math.pi)          # gelu, tanh approximation
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _layer(m, quant, x, lp):
    """One decoder layer over one sequence x [T, D] (float32)."""
    t, d = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(t)
    h = _norm(m, x, lp["ln1"])
    a = lp["attn"]
    q = _mm(h, a["wq"].reshape(d, nq * hd), quant).reshape(t, nq, hd)
    k = _mm(h, a["wk"].reshape(d, nkv * hd), quant).reshape(t, nkv, hd)
    v = _mm(h, a["wv"].reshape(d, nkv * hd), quant).reshape(t, nkv, hd)
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    g = nq // nkv
    qg = q.reshape(t, nkv, g, hd)
    scale = m.get("attention_multiplier") or 1.0 / math.sqrt(hd)
    s = jnp.einsum("qngh,knh->ngqk", qg, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    qi, kj = pos[:, None], pos[None, :]
    mask = kj <= qi
    if m.get("sliding_window"):
        mask &= kj > qi - m["sliding_window"]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngqk,knh->qngh", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, nq * hd)
    x = x + _mm(o, a["wo"].reshape(nq * hd, d), quant)
    h = _norm(m, x, lp["ln2"])
    f = lp["ffn"]
    if "w_gate" in f:
        u = _act(m, _mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    else:
        u = _act(m, _mm(h, f["w_in"], quant))
    return x + _mm(u, f["w_down"], quant)


def _head(m, quant, x, final_norm, table):
    """Final norm and output head: x [N, D] -> logits [N, V]."""
    h = _norm(m, x, jax.tree.map(lambda a: a.astype(jnp.float32),
                                 final_norm))
    return _mm(h, table.astype(jnp.float32).T, quant)


def _exit_entropy(m, quant, x, head):
    """Entropy in nats of an exit head's softmax: x [N, D] -> [N].  The
    head is its own norm and a linear map to the vocabulary (``w``
    [D, V])."""
    h = _norm(m, x, jax.tree.map(lambda a: a.astype(jnp.float32),
                                 head["norm"]))
    lg = _mm(h, head["w"].astype(jnp.float32), quant)
    p = jax.nn.softmax(lg, axis=-1)
    return jax.nn.logsumexp(lg, axis=-1) - jnp.sum(p * lg, axis=-1)


class Reference:
    """Teacher-forced logits of whole sequences, layer by layer.

    ``m`` is the configuration file's ``model`` section; ``params`` the
    parameter tree: ``embed`` [V, D], ``final_norm``, optional ``lm_head``
    [V, D], ``exit_heads`` (each a ``norm`` and ``w`` [D, V]) and
    ``blocks``, a list of layer stacks (leading axis = layer) holding
    ``ln1``, ``attn`` (wq [D, Nq, H], wk/wv [D, Nkv, H], wo [Nq, H, D]),
    ``ln2`` and ``ffn``.  Sequences come as one padded
    batch [S, T]: attention is causal, so padding after a sequence's end
    changes nothing before it, and one fixed shape compiles once.
    """

    def __init__(self, m: Dict, params, quant: str = "none"):
        assert quant in QUANTS, quant
        self.m, self.params, self.quant = m, params, quant

        def layer(x, block, li):
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, False),
                block)
            return jax.vmap(lambda xx: _layer(m, quant, xx, lp))(x)

        # the layer index is traced: one compile per stack shape, not per
        # layer
        self._layer = jax.jit(layer)

        def head(x, final_norm, table, look):
            lg = _head(m, quant, x, final_norm, table)
            at = jnp.take_along_axis(lg, look[:, None], axis=-1)[:, 0]
            return jnp.max(lg, -1), at, jnp.argmax(lg, -1).astype(jnp.int32)

        self._head = jax.jit(jax.vmap(head, in_axes=(0, None, None, 0)))
        self._exit = jax.jit(lambda x, head: _exit_entropy(m, quant, x,
                                                            head))

    def hidden(self, tokens: np.ndarray, taps=()):
        """Last layer's residual stream [S, T, D] for tokens [S, T], and
        {n: the stream after the first n layers} for each n in ``taps``."""
        p = self.params
        x = p["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        seen, tapped = 0, {}
        for block in p["blocks"]:
            n = jax.tree.leaves(block)[0].shape[0]
            for li in range(n):
                x = self._layer(x, block, np.int32(li))
                seen += 1
                if seen in taps:
                    tapped[seen] = x
        return (x, tapped) if taps else x

    def exit_entropy(self, x, exit_index: int) -> np.ndarray:
        """Entropy in nats of exit head ``exit_index`` over the residual
        stream x [N, D] read after its layer."""
        return np.asarray(self._exit(
            jnp.asarray(x, jnp.float32),
            self.params["exit_heads"][exit_index]))

    def head(self, x, look: np.ndarray):
        """Per position of x [S, T, D]: the best logit, the logit of token
        ``look`` [S, T], and the argmax token, each [S, T]."""
        p = self.params
        table = p["lm_head"] if "lm_head" in p else p["embed"]
        best, at, arg = self._head(x, p["final_norm"], table,
                                   jnp.asarray(look, jnp.int32))
        return np.asarray(best), np.asarray(at), np.asarray(arg)
