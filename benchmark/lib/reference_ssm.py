"""A plain float32 reference of the token model whose layers mix Mamba-2
state-space layers with attention (``--preset granite-4.0-h-micro-pp4``):
forward, loss, each state-space layer's mixer output and gradients:
through ``jax.grad`` at a test's size, and a block at a time
(:func:`gradients`) at the cell's.

``jax.numpy`` only, float32, ``jax.default_matmul_precision("highest")``;
no kernel, no chunked loss, no gradient written by hand. It reads the
program's parameter tree and the ``model`` block of a configuration file
and shares no code with the program (nor with the other references).
**The scan is taken in its quadratic (dual) form**, by blocks of query
positions against every earlier key position: ``y_t = sum_{s <= t} (C_t .
B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t``, an algorithm that
has no chunk and no state, so a fault in what the program carries from
chunk to chunk shows against it. Each block's decays are sums measured
from the block's first position (backwards over the keys before it,
forwards inside it), so that no two long cumulative sums are
subtracted. Attention is taken in blocks of query rows against an
explicit causal matrix.

**The equations** (granite-4.0-h-micro's ``config.json``,
``granitemoehybrid``; HF's ``modeling_granitemoehybrid.py``). ``h`` is
the residual stream ``[T, D]``; every norm is RMSNorm with a learned
scale at ``ln_epsilon``; ``r`` = ``residual_multiplier``.

0. *Embedding*: ``h = embedding_multiplier x E[ids]``.
1. *Mamba-2 mixer* (``mixer_layout`` 2), ``z = norm(h)``: ``[g | xBC |
   dt] = z W_in``; ``xBC = silu(sum_k w_k xBC_{t-K+1+k} + b)`` (0 before
   the sequence's first position); ``[x | B | C] = xBC`` (H heads of P,
   then G groups of N twice); ``dt = softplus(dt + dt_bias)``, ``A =
   -exp(A_log)``; the scan above, head h reading group ``h // (H / G)``;
   ``y * silu(g)`` RMS-normed over each group's ``H P / G`` columns times
   a learned scale; ``W_out``. No bias on either projection.
2. *Attention* (``mixer_layout`` 0): ``q, k, v`` from one projection (H
   and Hkv heads of Dh), no positions, causal softmax at scale
   ``attn_scale``, query head h reading key/value head ``h // (H /
   Hkv)``; ``o W_o``. No bias.
3. *Block*: ``h += r Mixer(norm(h))``; ``h += r (silu(z W_g) * (z W_u))
   W_d`` with ``z = norm(h)`` at ``dense_width``.
4. *Final*: ``logits = norm_f(h) E^T / logits_scaling`` (the head tied
   to the embedding); loss = mean cross entropy against the next token.

**Departures from the source**: the parameter layout is the program's
(``qkv`` one ``[D, H + 2 Hkv, Dh]`` array, the taps ``[K, C]`` where
torch's ``Conv1d`` holds ``[C, 1, K]``); only the vocabulary rows held
exist; weights are random.

The controls a driver asks for (each must fail its comparison): ``dtype``
rounds matmul inputs (and, in the family ``scan``, the scan's ``x``,
``B`` and ``C``); ``carry=False`` drops every pair whose key lies in an
earlier chunk of ``ssm_chunk`` positions (the state not carried);
``counted`` leaves positions out of the loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256     # attention: queries whose [H, block, T] exist at once
SCAN_BLOCK = 64       # scan: queries whose [H, block, T] decays exist at once
LOGIT_CHUNK = 2048    # positions whose [chunk, V] logits exist at a time
FAMILIES = ("ssm_proj", "scan", "attention", "dense", "head")


def _round(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _mm(a, b, spec: str, dtype=None):
    """``einsum`` in float32 at the highest precision; with ``dtype``
    both operands are first rounded to it."""
    return jnp.einsum(spec, _round(a, dtype), _round(b, dtype),
                      precision="highest")


def _low(dtype, only, family):
    """``dtype`` where the control rounds this ``family`` (``only``: a
    family, several, or None for all), else None."""
    if only is None:
        return dtype
    only = (only,) if isinstance(only, str) else tuple(only)
    assert all(f in FAMILIES for f in only), only
    return dtype if family in only else None


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def later(v, by: int):
    """``v [T, C]`` moved ``by`` positions later, zeros in front."""
    if by == 0:
        return v
    return jnp.concatenate([jnp.zeros((by,) + v.shape[1:], v.dtype),
                            v[:v.shape[0] - by]])


def scan_dual(x, dt, a, bb, cc, d, *, chunk=None, block: int = SCAN_BLOCK):
    """The scan of one sequence in its quadratic form: ``x [T, H, P]``,
    ``dt [T, H]``, ``a [H]``, ``bb``, ``cc [T, G, N]``, ``d [H]`` ->
    ``[T, H, P]``. ``chunk`` (a control) keeps only the pairs inside one
    chunk of that many positions: what a scan that carries no state
    between chunks computes."""
    t, h, p = x.shape
    g = bb.shape[1]
    log = dt * a                                             # [T, H]
    u = dt[..., None] * x                                    # [T, H, P]
    block = min(block, t)
    blocks = -(-t // block)
    pos = jnp.arange(t)

    def one(first):
        rows = first + jnp.arange(block)                     # [R]
        # decay(t, s) = exp(back[s] + ahead[t]): back[s] the log-decay
        # from s + 1 to the block's first position, ahead[t] from there
        # to t, each a sum that starts at the block
        before = jnp.where((pos <= first)[:, None], log, 0.0)
        back = jnp.flip(jnp.cumsum(jnp.flip(before, 0), 0), 0) - before
        inside = jnp.cumsum(jnp.where((pos > first)[:, None], log, 0.0), 0)
        key = jnp.where((pos <= first)[:, None], back, -inside)  # [T, H]
        ahead = jnp.take(inside, jnp.minimum(rows, t - 1), axis=0)
        ahead = jnp.where((rows > first)[:, None], ahead, 0.0)   # [R, H]
        visible = pos[None, :] <= rows[:, None]                  # [R, T]
        if chunk is not None:
            visible &= (pos[None, :] // chunk) == (rows[:, None] // chunk)
        expo = ahead.T[:, :, None] + key.T[:, None, :]           # [H, R, T]
        decay = jnp.exp(jnp.where(visible[None], expo, -jnp.inf))
        c_rows = jnp.take(cc, jnp.minimum(rows, t - 1), axis=0)  # [R, G, N]
        cb = jnp.einsum("rgn,sgn->grs", c_rows, bb, precision="highest")
        w = decay.reshape(g, h // g, block, t) * cb[:, None]
        return jnp.einsum("gkrs,sgkp->rgkp", w,
                          u.reshape(t, g, h // g, p),
                          precision="highest").reshape(block, h, p)

    # (taken again in a gradient: no block's [H, R, T] is kept)
    y = jax.lax.map(jax.checkpoint(one), jnp.arange(blocks) * block)
    return y.reshape(blocks * block, h, p)[:t] + d[:, None] * x


def mamba_mixer(z, p: dict, model: dict, *, dtype=None, only=None,
                carry: bool = True):
    """Equation 1 for the normed input ``z [T, D]``."""
    h, hp, g, n = (model["ssm_heads"], model["ssm_head_dim"],
                   model.get("ssm_groups", 1), model["ssm_state"])
    inner, width = h * hp, h * hp + 2 * g * n
    d_proj = _low(dtype, only, "ssm_proj")
    proj = _mm(z, p["in_proj"]["kernel"], "td,de->te", d_proj)
    gate, xbc, dt = (proj[:, :inner], proj[:, inner:inner + width],
                     proj[:, inner + width:])
    taps = p["conv_kernel"]
    k = taps.shape[0]
    xbc = jax.nn.silu(sum(taps[i] * later(xbc, k - 1 - i) for i in range(k))
                      + p["conv_bias"])
    d_scan = _low(dtype, only, "scan")
    t = z.shape[0]
    x = _round(xbc[:, :inner], d_scan).reshape(t, h, hp)
    bb = _round(xbc[:, inner:inner + g * n], d_scan).reshape(t, g, n)
    cc = _round(xbc[:, inner + g * n:], d_scan).reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = scan_dual(x, dt, -jnp.exp(p["A_log"]), bb, cc, p["D"],
                  chunk=None if carry else model["ssm_chunk"])
    v = (y.reshape(t, inner) * jax.nn.silu(gate)).reshape(t, g, inner // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + model["ln_epsilon"])
    v = v.reshape(t, inner) * p["gate_norm_scale"]
    return _mm(v, p["out_proj"]["kernel"], "te,ed->td", d_proj)


def causal_attention(q, k, v, scale, *, block: int = QUERY_BLOCK,
                     dtype=None):
    """Causal softmax attention of ``q [T, H, Dh]`` over ``k, v [T, Hkv,
    Dh]`` at ``scale``, a block of query rows at a time against an
    explicit causal matrix: ``[T, H, Dh]``."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    k_all, v_all = (jnp.repeat(x, group, axis=1) for x in (k, v))
    block = min(block, t)
    blocks = -(-t // block)
    rows = jnp.pad(q, ((0, blocks * block - t), (0, 0), (0, 0))).reshape(
        blocks, block, h, dh)

    def one(args):
        q_rows, first = args
        visible = (jnp.arange(t)[None, :]
                   <= (first + jnp.arange(block))[:, None])     # [R, T]
        s = _mm(q_rows, k_all, "qhd,khd->hqk", dtype) * scale
        prob = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return _mm(prob, v_all, "hqk,khd->qhd", dtype)

    out = jax.lax.map(jax.checkpoint(one), (rows, jnp.arange(blocks) * block))
    return out.reshape(blocks * block, h, dh)[:t]


def attention(z, p: dict, model: dict, *, dtype=None, only=None):
    """Equation 2 for the normed input ``z [T, D]``."""
    hq, hkv = model["num_heads"], model["num_kv_heads"]
    dh = model.get("head_dim_override") or model["embedding_dim"] // hq
    scale = model.get("attn_scale") or dh ** -0.5
    d_att = _low(dtype, only, "attention")
    qkv = _mm(z, p["qkv"]["kernel"], "td,dhe->the", d_att)
    q, k, v = qkv[:, :hq], qkv[:, hq:hq + hkv], qkv[:, hq + hkv:]
    o = causal_attention(q, k, v, scale, dtype=d_att)
    return _mm(o, p["out"]["kernel"], "the,hed->td", d_att)


def gated(z, p: dict, dtype=None):
    """``(silu(z W_g) * (z W_u)) W_d`` with ``p = {gate, up, down}``."""
    hidden = jax.nn.silu(_mm(z, p["gate"]["kernel"], "td,df->tf", dtype)) \
        * _mm(z, p["up"]["kernel"], "td,df->tf", dtype)
    return _mm(hidden, p["down"]["kernel"], "tf,fd->td", dtype)


def is_ssm(model: dict, layer: int) -> bool:
    lay = model.get("mixer_layout") or ()
    return bool(lay) and lay[layer % len(lay)] == 2


def block(x, p: dict, model: dict, layer: int, *, dtype=None, only=None,
          carry: bool = True):
    """One block for one sequence ``x [T, D]``: ``(x, the mixer's
    output)``."""
    eps, r = model["ln_epsilon"], model.get("residual_multiplier", 1.0)
    z = rms_norm(x, p["msa"]["norm"]["scale"], eps)
    if is_ssm(model, layer):
        mixed = mamba_mixer(z, p["msa"], model, dtype=dtype, only=only,
                            carry=carry)
    else:
        mixed = attention(z, p["msa"], model, dtype=dtype, only=only)
    x = x + r * mixed
    z = rms_norm(x, p["mlp"]["norm"]["scale"], eps)
    return x + r * gated(z, p["mlp"]["dense"], _low(dtype, only, "dense")), \
        mixed


_PROGRAMS: dict = {}


def _kind(model: dict, layer: int, dtype, only, carry) -> tuple:
    return (repr(sorted(model.items())), is_ssm(model, layer), dtype, only,
            carry)


def _block_program(model: dict, layer: int, dtype, only, carry):
    """:func:`block` compiled ONCE for every layer of the same kind (a
    layer's parameters are arguments): called eagerly at 16,384 tokens,
    the ten layers run two executables."""
    only = tuple(only) if isinstance(only, list) else only
    key = _kind(model, layer, dtype, only, carry)
    if key not in _PROGRAMS:
        fields = dict(model)
        _PROGRAMS[key] = jax.jit(lambda x, p: block(
            x, p, fields, layer, dtype=dtype, only=only, carry=carry))
    return _PROGRAMS[key]


def _block_vjp_program(model: dict, layer: int, dtype, only, carry):
    """``(x, p, dy) -> (dx, dp)``: :func:`block`'s stream output pulled
    back, its forward taken again inside, compiled once a kind."""
    only = tuple(only) if isinstance(only, list) else only
    key = ("vjp",) + _kind(model, layer, dtype, only, carry)
    if key not in _PROGRAMS:
        fields = dict(model)

        def pull(x, p, dy):
            _, back = jax.vjp(lambda x, p: block(
                x, p, fields, layer, dtype=dtype, only=only,
                carry=carry)[0], x, p)
            return back(dy)

        _PROGRAMS[key] = jax.jit(pull)
    return _PROGRAMS[key]


def _embed(table, ids, model):
    return table[ids] * model.get("embedding_multiplier", 1.0)


def hidden(params, tokens, model: dict, *, dtype=None, only=None,
           carry: bool = True, mixers=()):
    """``(final-norm hidden states [B, T, D], {layer: the mixer's output
    [B, T, D]} for the layers in ``mixers``)``, float32."""
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        table = backbone["token_embedding"]["embedding"]

        def one(ids):
            x, kept = _embed(table, ids, model), {}
            for layer in range(model["num_layers"]):
                x, mixed = _block_program(model, layer, dtype, only, carry)(
                    x, backbone[f"encoder_block_{layer}"])
                if layer in mixers:
                    kept[layer] = mixed
            return rms_norm(x, backbone["encoder_norm"]["scale"],
                            model["ln_epsilon"]), kept

        rows = [one(ids) for ids in tokens]
        return (jnp.stack([r[0] for r in rows]),
                {layer: jnp.stack([r[1][layer] for r in rows])
                 for layer in mixers})


def logits(params, hid, model: dict, *, dtype=None):
    """``hid [..., D]`` through the head tied to the token embedding,
    divided by ``logits_scaling``, float32."""
    table = jnp.asarray(params["backbone"]["token_embedding"]["embedding"],
                        jnp.float32)
    return _mm(hid, table, "...d,vd->...v", dtype) \
        / model.get("logits_scaling", 1.0)


def _nll_sum(table, hid, targets, weights, model, *, dtype=None,
             chunk: int = LOGIT_CHUNK):
    """``sum weights x cross entropy`` of ``hid [N, D]`` through the tied
    head, ``chunk`` positions' logits at a time."""
    total = 0.0
    for lo in range(0, hid.shape[0], chunk):
        lg = _mm(hid[lo:lo + chunk], table, "td,vd->tv", dtype) \
            / model.get("logits_scaling", 1.0)
        picked = jnp.take_along_axis(lg, targets[lo:lo + chunk, None], 1)
        nll = jax.nn.logsumexp(lg, axis=-1) - picked[:, 0]
        total = total + jnp.sum(weights[lo:lo + chunk] * nll)
    return total


def _weights(labels, counted):
    """Each position's weight in the mean: ``1 / positions counted``."""
    keep = np.broadcast_to(np.float32(1.0) if counted is None
                           else np.asarray(counted, np.float32),
                           np.shape(labels))
    return keep / max(keep.sum(), 1.0)


def loss(params, tokens, labels, model: dict, *, dtype=None, only=None,
         carry: bool = True, counted=None):
    """The mean next-token cross entropy over the positions ``counted``
    (every one by default)."""
    hid = hidden(params, tokens, model, dtype=dtype, only=only,
                 carry=carry)[0]
    table = jnp.asarray(params["backbone"]["token_embedding"]["embedding"],
                        jnp.float32)
    return _nll_sum(table, hid.reshape(-1, hid.shape[-1]),
                    jnp.asarray(labels).reshape(-1),
                    jnp.asarray(_weights(labels, counted)).reshape(-1),
                    model, dtype=_low(dtype, only, "head"))


def _head_vjp_program(model: dict, dtype, only):
    """``(x, scale, table, targets, weights) -> (the weighted summed cross
    entropy, its gradients in x, the final norm's scale and the table)``
    for one sequence's last block output ``x [T, D]``."""
    key = ("head", repr(sorted(model.items())), dtype, only)
    if key not in _PROGRAMS:
        eps, d_head = model["ln_epsilon"], _low(dtype, only, "head")
        fields = dict(model)

        def pull(x, scale, table, targets, weights):
            total, back = jax.vjp(lambda x, scale, table: _nll_sum(
                table, rms_norm(x, scale, eps), targets, weights, fields,
                dtype=d_head), x, scale, table)
            return (total,) + back(jnp.float32(1.0))

        _PROGRAMS[key] = jax.jit(pull)
    return _PROGRAMS[key]


@jax.jit
def _lookup_grad(d_table, ids, dx, multiplier):
    return d_table.at[ids].add(multiplier * dx)


def gradients(params, tokens, labels, model: dict, *, dtype=None,
              only=None, carry: bool = True, counted=None):
    """``(loss, gradients)`` of :func:`loss` at the size of one block's
    work: each sequence's forward keeps only the blocks' inputs, and its
    backward takes each block's forward again inside that block's
    ``jax.vjp``; the table's gradient is the head's plus the lookup's.
    The same mathematics as ``jax.grad(loss)``."""
    assert set(params) == {"backbone"}, sorted(params)
    only = tuple(only) if isinstance(only, list) else only
    weights = _weights(labels, counted)
    multiplier = jnp.float32(model.get("embedding_multiplier", 1.0))
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        table = backbone["token_embedding"]["embedding"]
        scale = backbone["encoder_norm"]["scale"]
        grads = jax.tree.map(jnp.zeros_like, backbone)
        total = 0.0
        for ids, targets, w in zip(tokens, labels, weights):
            ids = jnp.asarray(ids)
            xs = [_embed(table, ids, model)]
            for layer in range(model["num_layers"]):
                xs.append(_block_program(model, layer, dtype, only, carry)(
                    xs[-1], backbone[f"encoder_block_{layer}"])[0])
            part, dx, d_scale, d_table = _head_vjp_program(
                model, dtype, only)(xs.pop(), scale, table,
                                    jnp.asarray(targets), jnp.asarray(w))
            total = total + part
            grads["encoder_norm"]["scale"] += d_scale
            for layer in reversed(range(model["num_layers"])):
                name = f"encoder_block_{layer}"
                dx, dp = _block_vjp_program(model, layer, dtype, only, carry)(
                    xs.pop(), backbone[name], dx)
                grads[name] = jax.tree.map(jnp.add, grads[name], dp)
            grads["token_embedding"]["embedding"] = _lookup_grad(
                grads["token_embedding"]["embedding"] + d_table, ids, dx,
                multiplier)
        return total, {"backbone": grads}
