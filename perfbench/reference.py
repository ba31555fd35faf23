"""Plain reference of a DeepSeek-V2-family decoder, for the check of
``correct``. Imports nothing of the program.

A float32 ``jax.numpy`` forward pass under ``jax.default_matmul_precision
("highest")``, in the published form of the layer (not the absorbed one):

* MLA: q = [rmsnorm(h W_dq)] W_uq (q-LoRA when ``q_lora_rank``), c_kv =
  rmsnorm(h W_dkv), per-head keys [c_kv W_uk ; rope(h W_kr)] and values
  c_kv W_uv, causal softmax at 1/sqrt(nope + rope), output W_o. Rope is
  half-split with ``rope_theta`` (no yarn ramp, as in the program; the
  configuration lists it under ``not_honoured``).
* MLP: layer < ``first_k_dense_replace`` a dense SwiGLU, else softmax
  top-k routing (renormalised only with ``norm_topk_prob``), each token's
  k routed SwiGLU experts and the shared experts.

It runs layer by layer over a group of sequences that share a document
prefix (the document goes through each layer once), with the weights of
each layer part made again from the seed (``weights.layer_leaves``), so it
holds one part's weights at a time. Attention goes in blocks of query rows,
the routed experts in blocks of rows sorted by expert.

``low`` gives the control, one step below each precision the
configuration states: every matmul operand rounded to float8 e4m3
(per-tensor scale) before a float32 product, the step below the bfloat16
weights, and the latent cache entry (c_kv) rounded to int4 with a
per-token scale, the step below its float8 e4m3 storage (the rope key,
stored in bfloat16, goes to float8 with the rest).

``stale`` plants a fault in the reference: each served token's row sees the
document, the question and itself, but no earlier served token, as a decode
step that returns its cache state unchanged would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights as W

SCORE_ELEMS = 1 << 28          # float32 elements of one attention block
EXPERT_ROWS = 128              # rows per routed-expert block
FP8_MAX = 448.0


def _q4(x):
    """Round each row to int4 (-7..7) on its own scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / 7.0
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _q8(x):
    """Round to float8 e4m3 on a per-tensor scale, back in float32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, low, b_done=False):
    """einsum in float32 at the highest precision; with ``low`` both
    operands rounded to float8 first (``b_done``: b already is)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a = _q8(a)
        b = b if b_done else _q8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Half-split rotary embedding of x [..., S, (H,) dr] at positions pos."""
    dr = x.shape[-1]
    half = dr // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)
    if x.ndim == 3:
        ang = ang[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _swiglu(x, g, u, d, low):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", x, g, low))
               * _mm("td,df->tf", x, u, low), d, low)


def _moe(D, p, x, low):
    """Softmax top-k routed experts + shared experts over rows x [T, d]."""
    T, E, k, B = x.shape[0], D["E"], D["k"], EXPERT_ROWS
    probs = jax.nn.softmax(_mm("td,de->te", x, p["w_router"], low), -1)
    wts, ids = jax.lax.top_k(probs, k)
    if D["renorm"]:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    # pairs (token, expert) sorted by expert, each expert's run padded to a
    # whole number of B-row blocks, so every block belongs to one expert
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    se = flat[order]
    counts = jnp.bincount(flat, length=E)
    padded = (counts + B - 1) // B * B
    ends = jnp.cumsum(padded)
    starts = ends - padded
    rank = jnp.arange(T * k) - jnp.searchsorted(se, se, side="left")
    dest = starts[se] + rank
    n_blocks = (T * k + E * (B - 1) + B - 1) // B
    buf = jnp.zeros((n_blocks * B, x.shape[1]), jnp.float32)
    buf = buf.at[dest].set(x[order // k])
    block_e = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_blocks) * B, side="right"), E - 1)

    def block(i):
        e = block_e[i]
        xb = jax.lax.dynamic_slice_in_dim(buf, i * B, B)
        return _swiglu(xb, p["e_gate"][e], p["e_up"][e], p["e_down"][e], low)

    ybuf = jax.lax.map(block, jnp.arange(n_blocks)).reshape(-1, x.shape[1])
    pair = jnp.zeros((T * k, x.shape[1]), jnp.float32).at[order].set(
        ybuf[dest])
    out = jnp.einsum("tkd,tk->td", pair.reshape(T, k, -1), wts)
    if "s_gate" in p:
        out = out + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], low)
    return out


def _kv(D, p, h, pos, low):
    """Per-head keys (nope part), the shared rope key and values of rows h
    (with ``low``, rounded to float8 once here, not per query block)."""
    c_kv = _rms(_mm("sd,dc->sc", h, p["w_dkv"], low), p["kv_norm"], D["eps"])
    if low:
        c_kv = _q4(c_kv)
    k_r = _rope(_mm("sd,dr->sr", h, p["w_kr"], low), pos, D["theta"])
    kv = (_mm("sc,chd->shd", c_kv, p["w_uk"], low), k_r,
          _mm("sc,chd->shd", c_kv, p["w_uv"], low))
    if low:
        kv = tuple(_q8(t) for t in kv)
    return kv + (pos,)


def _attend(D, p, hq, pos_q, key_sets, low, cut=None):
    """MLA of query rows hq [Q, d] at positions pos_q against one or more
    key sets (each from ``_kv``; scores are joined, keys are not copied):
    key j is seen by query i when pos_j <= pos_i, and, with ``cut``, a key
    past position ``cut`` only by the query at its own position."""
    H, dh = D["H"], D["dh"]
    scale = 1.0 / np.sqrt(dh + D["dr"])
    n_keys = sum(ks[3].shape[0] for ks in key_sets)
    Q = hq.shape[0]
    bq = max(1, min(Q, SCORE_ELEMS // (H * n_keys)))
    while Q % bq:
        bq -= 1

    def block(i):
        h = jax.lax.dynamic_slice_in_dim(hq, i * bq, bq)
        pq = jax.lax.dynamic_slice_in_dim(pos_q, i * bq, bq)
        if D["ql"]:
            h = _rms(_mm("sd,dq->sq", h, p["w_dq"], low), p["q_norm"],
                     D["eps"])
        q = _mm("sq,qhe->she", h, p["w_uq"], low)
        q_c, q_r = q[..., :dh], _rope(q[..., dh:], pq, D["theta"])

        def seen(pk):
            m = pk[None, :] <= pq[:, None]
            if cut is not None:
                m &= (pk[None, :] <= cut) | (pk[None, :] == pq[:, None])
            return m[:, None, :]

        s = jnp.concatenate([
            jnp.where(seen(pk),
                      (_mm("qhd,khd->qhk", q_c, k_c, low, b_done=True)
                       + _mm("qhr,kr->qhk", q_r, k_r, low, b_done=True))
                      * scale, -jnp.inf)
            for k_c, k_r, _, pk in key_sets], -1)
        pr = jax.nn.softmax(s, -1)
        o, at = 0.0, 0
        for _, _, v, pk in key_sets:
            n = pk.shape[0]
            o = o + _mm("qhk,khd->qhd", pr[..., at:at + n], v, low,
                        b_done=True)
            at += n
        return _mm("qhd,hdm->qm", o, p["w_o"], low)

    return jax.lax.map(block, jnp.arange(Q // bq)).reshape(Q, -1)


@functools.partial(jax.jit, static_argnames=("Dk", "low", "last", "stale"))
def _attn_pass(Dk, p, x_doc, xs, starts, *, low, last, stale):
    """Residual + attention of one layer. x_doc [S_d, d] is the shared
    document at positions 0.., xs [R, L, d] the requests' own rows at
    positions S_d.. (each request sees the document and its own rows;
    with ``stale``, request r's rows past starts[r] see no earlier row past
    it). The document's rows are not updated in the last layer: nothing
    reads them again."""
    D = dict(Dk)
    with jax.default_matmul_precision("highest"):
        S = x_doc.shape[0]
        pos_d = jnp.arange(S)
        pos_s = S + jnp.arange(xs.shape[1])
        h_doc = _rms(x_doc, p["ln1"], D["eps"])
        doc_kv = _kv(D, p, h_doc, pos_d, low)

        def one(args):
            x, start = args
            h = _rms(x, p["ln1"], D["eps"])
            own = _kv(D, p, h, pos_s, low)
            keys = [doc_kv, own] if S else [own]
            return x + _attend(D, p, h, pos_s, keys, low,
                               cut=S + start if stale else None)

        xs = jax.lax.map(one, (xs, starts))
        if S and not last:
            x_doc = x_doc + _attend(D, p, h_doc, pos_d, [doc_kv], low)
        return x_doc, xs


@functools.partial(jax.jit, static_argnames=("Dk", "dense", "low"))
def _mlp_pass(Dk, p, x, *, dense, low):
    D = dict(Dk)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, p["ln2"], D["eps"])
        return x + (_swiglu(h, p["w_gate"], p["w_up"], p["w_down"], low)
                    if dense else _moe(D, p, h, low))


@functools.partial(jax.jit, static_argnames=("Dk", "low", "n_out"))
def _head(Dk, g, xs, starts, *, low, n_out):
    """Logits [R, n_out, V] of rows starts[r] .. starts[r] + n_out - 1."""
    D = dict(Dk)
    with jax.default_matmul_precision("highest"):
        rows = jnp.minimum(starts[:, None] + jnp.arange(n_out)[None, :],
                           xs.shape[1] - 1)
        x = jnp.take_along_axis(xs, rows[..., None], axis=1)
        return _mm("rtd,vd->rtv", _rms(x, g["ln_f"], D["eps"]),
                   g["unembed"], low)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_leaves(Dk, key, layer, part, dense):
    """One compile per (part, dense), not per layer: the layer is traced."""
    return W.layer_leaves(dict(Dk), key, layer, part, dense)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _global_leaves(Dk, key, part):
    return W.global_leaves(dict(Dk), key, part)


def logits(cfg: dict, key, doc: np.ndarray, rows: np.ndarray,
           starts: np.ndarray, n_out: int, low: bool = False,
           stale: bool = False):
    """Reference logits [R, n_out, V] (float32, on the device) of R
    sequences that share the prefix ``doc`` [S_d] (S_d may be 0): sequence
    r is doc + rows[r] ([R, L] int32, padded at the end, which is harmless:
    attention is causal); its logits are read at rows[r] positions
    starts[r] .. starts[r] + n_out - 1. Layer by layer, with one part's
    weights (embedding, attention, MLP, head) live at a time; the document
    goes through each layer once for all R sequences. ``low`` and
    ``stale`` as in the module's docstring (the served tokens of sequence r
    are its rows past starts[r])."""
    D = W.dims(cfg)
    Dk = tuple(sorted(D.items()))
    key = jnp.asarray(key)
    g = _global_leaves(Dk, key, "embed")
    x_doc = g["embed"][jnp.asarray(doc, jnp.int32)].astype(jnp.float32)
    xs = g["embed"][jnp.asarray(rows, jnp.int32)].astype(jnp.float32)
    del g
    R, L = rows.shape
    starts = jnp.asarray(starts, jnp.int32)
    for layer in range(D["L"]):
        last = layer == D["L"] - 1
        dense = W.layer_is_dense(D, layer)
        p = _layer_leaves(Dk, key, jnp.int32(layer), "attn", dense)
        x_doc, xs = _attn_pass(Dk, p, x_doc, xs, starts, low=low,
                               last=last, stale=stale)
        del p
        if last:
            x_doc = x_doc[:0]
        p = _layer_leaves(Dk, key, jnp.int32(layer), "mlp", dense)
        xs = _mlp_pass(Dk, p, xs.reshape(R * L, -1), dense=dense,
                       low=low).reshape(R, L, -1)
        if len(doc) and not last:
            x_doc = _mlp_pass(Dk, p, x_doc, dense=dense, low=low)
        del p
    g = _global_leaves(Dk, key, "head")
    return _head(Dk, g, xs, starts, low=low, n_out=n_out)


@jax.jit
def gaps(ref, tokens):
    """How far the reference logit of each token lies below its row's best,
    in units of the row's standard deviation: ref [..., V], tokens [...]."""
    with jax.default_matmul_precision("highest"):
        got = jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]
        return (jnp.max(ref, -1) - got) / jnp.std(ref, -1)
