"""Plain reference of the dense decoder that both granite configurations run.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision, no
cache, no batching, no kernels; it imports nothing of the program.  The
equations are the program's dense block (pre-norm, RMSNorm with a ``1 +
scale`` gain, rotary embedding on the two halves of each head, grouped-query
causal softmax attention scaled by 1/sqrt(head_dim), SwiGLU MLP, final norm,
tied or untied head).  The published granite models also scale the
embedding, the residual branches, the attention logits and the output logits
by constants; the program's dense arch does not, so neither does this
reference, and the configuration files name them under ``program_gaps``.

``logits`` runs one sequence, layer by layer (a scan over the stacked
layers, each upcast to float32 inside its own iteration) and attention in
blocks of query rows, so it fits beside the weights on one chip.
``quant=True`` is the control: the same computation with every weight
matrix rounded to int8 with one scale per output channel, the precision
below the bfloat16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_ROWS = 512


def _int8(w, axis):
    """Round to int8 with one scale per slice along ``axis`` (the input axis
    is reduced), and return the dequantized float32 weight."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _w(w, quant, axis=-2):
    return _int8(w, axis) if quant else w.astype(jnp.float32)


def _norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, G):
    """Causal GQA over one sequence, in blocks of ``Q_ROWS`` query rows.
    q (T, H, D); k, v (T, Hkv, D)."""
    T, H, D = q.shape
    k = jnp.repeat(k, G, axis=1)  # query head h reads kv head h // G
    v = jnp.repeat(v, G, axis=1)
    cols = jnp.arange(T)
    outs = []
    for r0 in range(0, T, Q_ROWS):
        qb = q[r0:r0 + Q_ROWS]
        rows = jnp.arange(r0, r0 + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HI) / np.sqrt(D)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, precision=HI))
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnames=("m", "quant"))
def logits(params, tokens, *, m: tuple, quant: bool = False):
    """(T, V) float32 logits of one sequence; ``m`` is ``families/dense.dims``
    as a sorted tuple of items."""
    m = dict(m)
    H, Hkv, hd, eps = m["H"], m["Hkv"], m["hd"], m["eps"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    emb = _w(params["embed"], quant, axis=-1)
    x = emb[tokens]

    def layer(x, p):
        a, f = p["attn"], p["mlp"]
        h = _norm(x, p["ln1"], eps)
        q = jnp.dot(h, _w(a["wq"], quant), precision=HI).reshape(T, H, hd)
        k = jnp.dot(h, _w(a["wk"], quant), precision=HI).reshape(T, Hkv, hd)
        v = jnp.dot(h, _w(a["wv"], quant), precision=HI).reshape(T, Hkv, hd)
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        att = _attention(q, k, v, H // Hkv).reshape(T, H * hd)
        x = x + jnp.dot(att, _w(a["wo"], quant), precision=HI)
        h = _norm(x, p["ln2"], eps)
        g = jnp.dot(h, _w(f["w_gate"], quant), precision=HI)
        u = jnp.dot(h, _w(f["w_up"], quant), precision=HI)
        return x + jnp.dot(jax.nn.silu(g) * u, _w(f["w_down"], quant), precision=HI), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _norm(x, params["final_ln"], eps)
    head = emb.T if m["tied"] else _w(params["lm_head"], quant)
    return jnp.dot(x, head, precision=HI)


@partial(jax.jit, static_argnames=("mt", "md", "control"))
def readings(tp, dp, tokens, at, served, first_t, first_d, *, mt: tuple, md: tuple,
             control: bool = False):
    """Per position ``at[i]`` of the sequence ``tokens``, where the program
    served ``served[i]`` and its target and draft put ``first_t[i]`` and
    ``first_d[i]`` first; positions past the sequence's real end carry
    ``at = -1`` and read 0.  With the reference target's log-probabilities
    ``lp`` and draft's ``lq`` there:

    - ``gap_target``, ``gap_draft``: how far the reference logit of the
      program's first choice lies below the reference's best;
    - ``nll``, ``nll_mean``, ``nll_var``: ``-lp`` of the served token, and
      its mean (the entropy) and variance were the token drawn from ``lp``;
    - ``llr``, ``llr_mean``, ``llr_var``: the same of ``lp - lq``, the log
      ratio that tells the target's draws from the draft's;
    - with ``control``, ``control_target``, ``control_draft``: the gap of the
      token the int8 control puts first."""
    live = at >= 0
    idx = jnp.maximum(at, 0)
    lt = logits(tp, tokens, m=mt)[idx]
    ld = logits(dp, tokens, m=md)[idx]

    def gap(ref, first):
        best = jnp.max(ref, axis=-1)
        return jnp.where(live, best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0],
                         0.0)

    def moments(x, p, pick):
        mean = jnp.sum(p * x, axis=-1)
        var = jnp.sum(p * jnp.square(x - mean[:, None]), axis=-1)
        at_tok = jnp.take_along_axis(x, pick[:, None], axis=-1)[:, 0]
        return [jnp.where(live, v, 0.0) for v in (at_tok, mean, var)]

    lp = jax.nn.log_softmax(lt, axis=-1)
    p = jnp.exp(lp)
    out = {"gap_target": gap(lt, first_t), "gap_draft": gap(ld, first_d)}
    out.update(zip(("nll", "nll_mean", "nll_var"), moments(-lp, p, served)))
    llr = lp - jax.nn.log_softmax(ld, axis=-1)
    out.update(zip(("llr", "llr_mean", "llr_var"), moments(llr, p, served)))
    if control:
        for name, params, m, ref in (("target", tp, mt, lt), ("draft", dp, md, ld)):
            ctl = logits(params, tokens, m=m, quant=True)[idx]
            out[f"control_{name}"] = gap(ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32))
    return out
