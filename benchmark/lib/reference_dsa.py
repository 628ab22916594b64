"""A plain float32 reference of the token model whose attention an indexer
selects (``--preset keye-vl-2.0-30b-a3b-ep8``): forward, both losses, the
selection itself and, through ``jax.grad``, gradients.

``jax.numpy`` only, float32, ``jax.default_matmul_precision("highest")``;
no kernel, no bisection, no packed mask, no recomputation, no gradient
written by hand. It reads the program's parameter tree and the ``model``
block of a configuration file and shares no code with the program (nor
with ``reference_lm.py`` / ``reference_mla.py``: the few helpers all need
are written again here). Attention is taken in blocks of query rows, so
that 16,384 tokens fit one chip; at sizes where a block is the whole
sequence that is the dense ``[T, T]`` form. The routed feed-forward is a
dense loop over the held experts with a ``where``. It takes
``experts_held`` / ``expert_offset`` and the vocabulary slice as the
program does.

**The equations** (Keye-VL-2.0-30B-A3B's ``config.json``, ``sa_config``;
DeepSeek-V3.2's description of sparse attention, arXiv:2512.02556). ``h``
is the residual stream ``[T, D]``, every norm RMSNorm (scale only) at
``ln_epsilon`` but the indexer key's LayerNorm; ``u = norm(h)``.

1. *Main path*: ``[q | k | v] = u W_qkv`` (H query, Hkv key and value
   heads of Dh); ``q_h``, ``k_g`` RMSNorm'd over their Dh columns with
   one learned scale for q and one for k (*assumed*: the family's
   convention); rotary on both (rotate-half, theta from the config);
   head h reads key/value head ``h // (H / Hkv)``.
2. *Indexer*, on ``u`` with the gradient cut: ``qI_j = rot(u W_qI)`` (J
   heads of Di, rotary over all Di columns), ``kI = rot(LayerNorm(u
   W_kI))`` (ONE head), ``w = (u W_w) J^-1/2 Di^-1/2``; ``I[t, s] = sum_j
   w[t, j] relu(qI[t, j] . kI[s])``.
3. *Selection*: ``S_t`` = the ``min(t + 1, topk)`` positions s <= t of
   the largest ``I[t, s]``, ties to the lower s (``lax.top_k``'s order;
   -0 is read as +0 first).
4. *Core*: ``o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g] /
   sqrt(Dh)) v[s, g]``; ``h += concat(o) W_o``.
5. *Alignment loss* of a layer: ``L_I = mean_t sum_{s in S_t} pbar[t, s]
   (log pbar[t, s] - log softmax_{S_t}(I[t, .])[s])`` with ``pbar = mean_h
   p[t, h, .]`` the core's probabilities, a constant (0 log 0 = 0).
6. *Feed-forward*, ``u' = norm(h)``: the ``experts_per_token`` largest
   router logits ``u' W_r``, softmax over those; ``h += sum over e
   selected AND held of w_e (silu(u' W_g,e) * (u' W_u,e)) W_d,e``.
7. *Final*: ``logits = norm_f(h_L) W_head``; main loss = mean cross
   entropy against the next token; objective ``main + sum over layers
   of L_I`` (weight 1 a layer).

**Departures from the source**: the parameter layout is the program's
(``qkv`` one ``[D, H + 2 Hkv, Dh]`` array); only the experts held and
the vocabulary rows held exist; text positions only (the three ``mrope``
streams equal); weights are random.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256     # queries whose [H, block, T] logits exist at a time
LOGIT_CHUNK = 2048    # positions whose [chunk, V] logits exist at a time
FAMILIES = ("attn_core", "indexer", "projections", "experts", "head")


def _mm(a, b, spec: str, dtype=None):
    """``einsum`` in float32 at the highest precision; with ``dtype``
    both operands are first rounded to it (how a forward with narrower
    matmul inputs is told apart from this one)."""
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision="highest")


def _low(dtype, only, family):
    """``dtype`` where the control rounds this product ``family``
    (``only``: a family, several, or None for all), else None."""
    if only is None:
        return dtype
    only = (only,) if isinstance(only, str) else tuple(only)
    assert all(f in FAMILIES for f in only), only
    return dtype if family in only else None


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, theta):
    """``x [T, H, R]`` turned by its positions, rotate-half over R."""
    t, _, r = x.shape
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    cos = np.concatenate([np.cos(angle), np.cos(angle)], -1)[:, None, :]
    sin = np.concatenate([np.sin(angle), np.sin(angle)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + turned * sin


def indexer(u, p: dict, model: dict, dtype=None):
    """Equation 2's three projections of ``u [T, D]``: ``(qI [T, J, Di],
    kI [T, Di], w [T, J])``."""
    heads, width = model["sa_index_heads"], model["sa_index_head_dim"]
    u = jax.lax.stop_gradient(u)
    q_i = rotary(_mm(u, p["index_q"]["kernel"], "td,dje->tje", dtype),
                 model["rope_theta"])
    k_i = layer_norm(_mm(u, p["index_k"]["kernel"], "td,de->te", dtype),
                     p["index_k_norm"]["scale"], p["index_k_norm"]["bias"],
                     model["ln_epsilon"])
    k_i = rotary(k_i[:, None, :], model["rope_theta"])[:, 0]
    w = _mm(u, p["index_w"]["kernel"], "td,dj->tj", dtype) \
        * heads ** -0.5 * width ** -0.5
    return q_i, k_i, w


def select(scores, rows, topk: int):
    """Equation 3 for the query rows at positions ``rows [R]`` with
    scores ``[R, T]``: booleans ``[R, T]``."""
    t = scores.shape[-1]
    causal = jnp.arange(t)[None, :] <= rows[:, None]
    _, ids = jax.lax.top_k(jnp.where(causal, scores + 0.0, -jnp.inf),
                           min(topk, t))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], ids].set(True)
    return picked & causal     # (a row with fewer causal keys than topk)


def indexed_attention(q, k, v, q_i, k_i, w, model: dict, *,
                      block: int = QUERY_BLOCK, dtype=None, d_index=None):
    """Equations 2-5 for one sequence: ``(o [T, H, Dh], L_I, selection
    [T, T] booleans)``, a block of query rows at a time. ``dtype`` rounds
    the core's products, ``d_index`` the indexer's."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    block = min(block, t)
    blocks = -(-t // block)
    pad = lambda x: jnp.pad(x, ((0, blocks * block - t),)
                            + ((0, 0),) * (x.ndim - 1))
    cut = lambda x: x.reshape((blocks, block) + x.shape[1:])
    k_all, v_all = (jnp.repeat(x, group, axis=1) for x in (k, v))

    def one(args):
        q_rows, qi_rows, w_rows, first = args
        rows = first + jnp.arange(block)
        act = jax.nn.relu(_mm(qi_rows, k_i, "rje,se->rjs", d_index))
        scores = jnp.einsum("rj,rjs->rs", w_rows, act, precision="highest")
        chosen = jax.lax.stop_gradient(
            select(jax.lax.stop_gradient(scores), rows, model["sa_topk"]))
        s = _mm(q_rows, k_all, "qhd,khd->hqk", dtype) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        out = _mm(p, v_all, "hqk,khd->qhd", dtype)
        pbar = jax.lax.stop_gradient(jnp.mean(p, axis=0))        # [R, T]
        log_soft = jax.nn.log_softmax(
            jnp.where(chosen, scores, -jnp.inf), axis=-1)
        cross = jnp.where(
            chosen & (pbar > 0),
            pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                    - jnp.where(chosen, log_soft, 0.0)), 0.0)
        real = rows < t                                   # rows of padding
        return (out, jnp.sum(jnp.where(real, jnp.sum(cross, -1), 0.0)),
                chosen & real[:, None])

    out, cross, chosen = jax.lax.map(
        one, (cut(pad(q)), cut(pad(q_i)), cut(pad(w)),
              jnp.arange(blocks) * block))
    return (out.reshape(blocks * block, h, dh)[:t], jnp.sum(cross) / t,
            chosen.reshape(blocks * block, t)[:t])


def attention(u, p: dict, model: dict, *, dtype=None, only=None):
    """Equations 1-5 for the normed input ``u [T, D]``: ``(concat(o) W_o,
    L_I, selection)``."""
    eps, hq, hkv = (model["ln_epsilon"], model["num_heads"],
                    model["num_kv_heads"])
    d_proj = _low(dtype, only, "projections")
    qkv = _mm(u, p["qkv"]["kernel"], "td,dhe->the", d_proj)
    q, k, v = qkv[:, :hq], qkv[:, hq:hq + hkv], qkv[:, hq + hkv:]
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), model["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), model["rope_theta"])
    d_index = _low(dtype, only, "indexer")
    q_i, k_i, w = indexer(u, p, model, d_index)
    o, loss, chosen = indexed_attention(
        q, k, v, q_i, k_i, w, model, dtype=_low(dtype, only, "attn_core"),
        d_index=d_index)
    return _mm(o, p["out"]["kernel"], "the,hed->td", d_proj), loss, chosen


def routed_ffn(u, p: dict, model: dict, *, dtype=None, offset=None):
    """Equation 6 for ``u [T, D]``: the part of the experts that ``p``
    holds, experts ``offset .. + E_held`` (``offset`` defaults to the
    model's)."""
    offset = model.get("expert_offset", 0) if offset is None else offset
    vals, ids = jax.lax.top_k(_mm(u, p["router"]["kernel"], "td,de->te"),
                              model["experts_per_token"])
    weights = jax.nn.softmax(vals, axis=-1)
    y = jnp.zeros_like(u)
    for e in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        hidden = jax.nn.silu(_mm(u, p["gate"][e], "td,df->tf", dtype)) \
            * _mm(u, p["up"][e], "td,df->tf", dtype)
        y = y + weight[:, None] * _mm(hidden, p["down"][e], "tf,fd->td",
                                      dtype)
    return y


def block(x, p: dict, model: dict, *, dtype=None, only=None):
    """One block for one sequence ``x [T, D]``: ``(x, L_I, selection)``.
    ``only`` confines ``dtype`` to families of products
    (:data:`FAMILIES`)."""
    eps = model["ln_epsilon"]
    attn, loss, chosen = attention(
        rms_norm(x, p["msa"]["norm"]["scale"], eps), p["msa"], model,
        dtype=dtype, only=only)
    x = x + attn
    u = rms_norm(x, p["mlp"]["norm"]["scale"], eps)
    return (x + routed_ffn(u, p["mlp"], model,
                           dtype=_low(dtype, only, "experts")), loss, chosen)


_BLOCK_PROGRAMS: dict = {}


def _block_program(model: dict, dtype, only):
    """:func:`block` for one ``model``, compiled ONCE for all its layers
    (a layer's parameters are arguments). Called under an outer
    ``jax.jit`` or ``jax.grad`` it is inlined there; called eagerly, as
    the benchmark's driver calls :func:`hidden` at 16,384 tokens, every
    layer runs the same executable. As one program over six layers the
    reference held six copies of ``lax.top_k``'s sorting network over
    16,384 keys: a 138 MB executable, which alone pushed everything else
    out of a 192 MiB compile cache in every run (PERF.md section 6)."""
    only = tuple(only) if isinstance(only, list) else only
    key = (repr(sorted(model.items())), dtype, only)
    if key not in _BLOCK_PROGRAMS:
        fields = dict(model)
        _BLOCK_PROGRAMS[key] = jax.jit(lambda x, p: block(
            x, p, fields, dtype=dtype, only=only))
    return _BLOCK_PROGRAMS[key]


def hidden(params, tokens, model: dict, *, dtype=None, only=None,
           selections=()):
    """``(final-norm hidden states [B, T, D], sum over layers of L_I (the
    sequences' mean), {layer: selection [B, T, T]} for the layers in
    ``selections``)``, float32."""
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        eps, layers = model["ln_epsilon"], model["num_layers"]
        table = backbone["token_embedding"]["embedding"]
        one_block = _block_program(model, dtype, only)

        def one(ids):
            x, total, kept = table[ids], 0.0, {}
            for layer in range(layers):
                x, loss, chosen = one_block(
                    x, backbone[f"encoder_block_{layer}"])
                total = total + loss
                if layer in selections:
                    kept[layer] = chosen
            return (rms_norm(x, backbone["encoder_norm"]["scale"], eps),
                    total, kept)

        rows = [one(ids) for ids in tokens]
        return (jnp.stack([r[0] for r in rows]),
                sum(r[1] for r in rows) / len(rows),
                {layer: jnp.stack([r[2][layer] for r in rows])
                 for layer in selections})


def logits(params, hid, *, dtype=None):
    """``hid [..., D]`` through the untied head, float32."""
    return _mm(hid, jnp.asarray(params["head"]["kernel"], jnp.float32),
               "...d,dv->...v", dtype)


def losses(params, tokens, labels, model: dict, *, dtype=None, only=None,
           chunk: int = LOGIT_CHUNK):
    """``(main, indexer)``: the mean next-token cross entropy over every
    position, and the sum over layers of the alignment loss."""
    hid, indexer_loss, _ = hidden(params, tokens, model, dtype=dtype,
                                  only=only)
    d_head = _low(dtype, only, "head")
    flat, targets = hid.reshape(-1, hid.shape[-1]), labels.reshape(-1)
    total = 0.0
    for lo in range(0, flat.shape[0], chunk):
        lg = logits(params, flat[lo:lo + chunk], dtype=d_head)
        total = total + jnp.sum(
            jax.nn.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, targets[lo:lo + chunk, None], 1)[:, 0])
    return total / flat.shape[0], indexer_loss


def loss(params, tokens, labels, model: dict, **kw):
    """The objective: ``main + sum of the layers'
    L_I``."""
    main, indexer_loss = losses(params, tokens, labels, model, **kw)
    return main + indexer_loss


def forward(params, tokens, model: dict, *, dtype=None, only=None):
    """Logits ``[B, T, V]`` over the vocabulary rows held."""
    return logits(params, hidden(params, tokens, model, dtype=dtype,
                                 only=only)[0],
                  dtype=_low(dtype, only, "head"))


def selections(params, tokens, model: dict, layers, **kw):
    """``{layer: [B, T, T] booleans}``: equation 3's sets."""
    return hidden(params, tokens, model, selections=tuple(layers), **kw)[2]


def selection_agreement(got, want) -> float:
    """The share of (query, selected key) pairs of ``want`` that ``got``
    selects too (both select the same number a query, so it is
    symmetric)."""
    got, want = np.asarray(got) != 0, np.asarray(want) != 0
    return float(np.sum(got & want) / max(np.sum(want), 1))


def agreement(got, want) -> dict:
    """How far ``got`` is from the reference ``want``, both in units of
    the reference's own spread (its standard deviation over all
    entries): ``rms`` and ``max``. The limit is set on ``rms``: one
    query whose 2,048th and 2,049th scores lie closer than bfloat16
    resolves attends otherwise in the program and owns ``max``, while a
    lower precision anywhere moves every entry and shows in ``rms``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    std = max(want.std(), 1e-12)
    diff = got - want
    return {"rms": float(np.sqrt(np.mean(diff * diff)) / std),
            "max": float(np.max(np.abs(diff)) / std)}
