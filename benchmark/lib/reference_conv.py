"""A plain float32 reference of the token model whose layers mix gated
short convolutions with attention (``--preset lfm2-24b-a2b-ep8``):
forward, loss, each conv layer's mixer output and gradients: through
``jax.grad`` at a test's size, and a block at a time (:func:`gradients`)
at the cell's.

``jax.numpy`` only, float32, ``jax.default_matmul_precision("highest")``;
no kernel, no chunked loss, no gradient written by hand. It reads the
program's parameter tree and the ``model`` block of a configuration file
and shares no code with the program (nor with the other references: the
few helpers all need are written again here). Attention is taken in
blocks of query rows against an explicit causal matrix, so that 8,192
tokens fit one chip; at sizes where a block is the whole sequence that is
the dense ``[T, T]`` form. The routed feed-forward is a dense loop over
the held experts with a ``where``. It takes ``experts_held`` /
``expert_offset`` and the vocabulary slice as the program does.

**The equations** (LFM2-24B-A2B's ``config.json``, ``lfm2_moe``; the
LFM2 family's ``Lfm2ShortConv.slow_forward`` and ``Lfm2Attention``).
``h`` is the residual stream ``[T, D]``; every norm is RMSNorm with a
learned scale at ``ln_epsilon``; block l is ``h += Mixer_l(norm(h))``,
then ``h += FFN_l(norm(h))``.

1. *Conv mixer* (``mixer_layout`` 1): ``[B | C | u] = z W_in`` (``D ->
   3D``, thirds in that order); ``v = B * u``; ``c_t = sum_k w_k *
   v_{t-K+1+k}`` with ``v`` 0 before the sequence's first position (a
   depthwise causal convolution, written here as K shifted sums);
   ``y = (C * c) W_out``. No bias.
2. *Attention* (``mixer_layout`` 0): ``q = z W_q`` (H heads of Dh),
   ``k``, ``v`` (Hkv heads); RMSNorm over each q and k head (one learned
   scale of Dh for each); rotate-half rotary over all Dh columns at
   ``rope_theta``; causal softmax attention, query head h reading
   key/value head ``h // (H / Hkv)``; ``o W_o``. No bias.
3. *Feed-forward*, ``z' = norm(h)``: the first ``dense_layers`` blocks
   ``(silu(z' W_g) * (z' W_u)) W_d`` at ``dense_width``; the others
   routed: scores ``s = sigmoid(z' W_r)`` over all ``num_experts``, the
   ``experts_per_token`` largest ``s + b`` selected (``b`` the expert
   bias: it moves the selection and takes no gradient), weights
   ``router_scale x s_e / sum of the selected s``; ``h += sum over e
   selected AND held of w_e (silu(z' W_g,e) * (z' W_u,e)) W_d,e``.
4. *Final*: ``logits = norm_f(h_L) E^T`` with ``E`` the token
   embedding's table (the head is tied to it); loss = mean cross entropy
   against the next token.

**Departures from the source**: the parameter layout is the program's
(``qkv`` one ``[D, H + 2 Hkv, Dh]`` array, the taps ``[K, D]`` where
torch's ``Conv1d`` holds ``[D, 1, K]``); only the experts held and the
vocabulary rows held exist; weights are random.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256     # queries whose [H, block, T] logits exist at a time
LOGIT_CHUNK = 2048    # positions whose [chunk, V] logits exist at a time
FAMILIES = ("conv", "projections", "attn_core", "dense", "experts", "head")


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


def rotary(x, theta):
    """``x [T, H, R]`` turned by its positions, rotate-half over R."""
    t, _, r = x.shape
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    cos = np.concatenate([np.cos(angle), np.cos(angle)], -1)[:, None, :]
    sin = np.concatenate([np.sin(angle), np.sin(angle)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + turned * sin


def later(v, by: int):
    """``v [T, D]`` moved ``by`` positions later, zeros in front."""
    if by == 0:
        return v
    return jnp.concatenate([jnp.zeros((by,) + v.shape[1:], v.dtype),
                            v[:v.shape[0] - by]])


def conv_mixer(z, p: dict, dtype=None):
    """Equation 1 for the normed input ``z [T, D]``."""
    d = z.shape[-1]
    bcu = _mm(z, p["in_proj"]["kernel"], "td,de->te", dtype)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    v = b * u
    taps = p["taps"]
    k = taps.shape[0]
    conv = sum(taps[i] * later(v, k - 1 - i) for i in range(k))
    return _mm(c * conv, p["out_proj"]["kernel"], "td,de->te", dtype)


def causal_attention(q, k, v, *, block: int = QUERY_BLOCK, dtype=None):
    """Causal softmax attention of ``q [T, H, Dh]`` over ``k, v [T, Hkv,
    Dh]``, a block of query rows at a time against an explicit causal
    matrix: ``[T, H, Dh]``."""
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
        s = _mm(q_rows, k_all, "qhd,khd->hqk", dtype) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return _mm(p, v_all, "hqk,khd->qhd", dtype)

    # (taken again in a gradient: no block's [H, R, T] is kept)
    out = jax.lax.map(jax.checkpoint(one), (rows, jnp.arange(blocks) * block))
    return out.reshape(blocks * block, h, dh)[:t]


def attention(z, p: dict, model: dict, layer: int, *, dtype=None,
              only=None):
    """Equation 2 for the normed input ``z [T, D]``."""
    eps, hq, hkv = (model["ln_epsilon"], model["num_heads"],
                    model["num_kv_heads"])
    d_proj = _low(dtype, only, "projections")
    qkv = _mm(z, p["qkv"]["kernel"], "td,dhe->the", d_proj)
    q, k, v = qkv[:, :hq], qkv[:, hq:hq + hkv], qkv[:, hq + hkv:]
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    rope = model["rope_layout"]
    if rope and rope[layer % len(rope)]:
        q, k = rotary(q, model["rope_theta"]), rotary(k, model["rope_theta"])
    o = causal_attention(q, k, v, dtype=_low(dtype, only, "attn_core"))
    return _mm(o, p["out"]["kernel"], "the,hed->td", d_proj)


def gated(z, p: dict, dtype=None):
    """``(silu(z W_g) * (z W_u)) W_d`` with ``p = {gate, up, down}``
    (each ``{kernel}``)."""
    hidden = jax.nn.silu(_mm(z, p["gate"]["kernel"], "td,df->tf", dtype)) \
        * _mm(z, p["up"]["kernel"], "td,df->tf", dtype)
    return _mm(hidden, p["down"]["kernel"], "tf,fd->td", dtype)


def route(z, p: dict, model: dict):
    """``(ids [T, k], weights [T, k])`` of equation 3's router."""
    s = jax.nn.sigmoid(_mm(z, p["router"]["kernel"], "td,de->te"))
    _, ids = jax.lax.top_k(s + p["router_bias"], model["experts_per_token"])
    picked = jnp.take_along_axis(s, ids, axis=-1)
    return ids, model["router_scale"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def routed_ffn(z, p: dict, model: dict, *, dtype=None, offset=None):
    """Equation 3's routed layer for ``z [T, D]``: the part of the
    experts that ``p`` holds, experts ``offset .. + E_held`` (``offset``
    defaults to the model's)."""
    offset = model.get("expert_offset", 0) if offset is None else offset
    ids, weights = route(z, p, model)

    def one(y, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        hidden = jax.nn.silu(_mm(z, gate, "td,df->tf", dtype)) \
            * _mm(z, up, "td,df->tf", dtype)
        return y + weight[:, None] * _mm(hidden, down, "tf,fd->td",
                                         dtype), None

    # a loop, not unrolled: one expert's program, however many are held
    held = p["gate"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(z), (
        jnp.arange(held), p["gate"], p["up"], p["down"]))[0]


def is_conv(model: dict, layer: int) -> bool:
    lay = model.get("mixer_layout") or ()
    return bool(lay and lay[layer % len(lay)])


def block(x, p: dict, model: dict, layer: int, *, dtype=None, only=None):
    """One block for one sequence ``x [T, D]``: ``(x, the mixer's
    output)``. ``only`` confines ``dtype`` to families of products
    (:data:`FAMILIES`)."""
    eps = model["ln_epsilon"]
    z = rms_norm(x, p["msa"]["norm"]["scale"], eps)
    if is_conv(model, layer):
        mixed = conv_mixer(z, p["msa"], _low(dtype, only, "conv"))
    else:
        mixed = attention(z, p["msa"], model, layer, dtype=dtype, only=only)
    x = x + mixed
    z = rms_norm(x, p["mlp"]["norm"]["scale"], eps)
    if layer < model.get("dense_layers", 0):
        return x + gated(z, p["mlp"]["dense"], _low(dtype, only, "dense")), \
            mixed
    return x + routed_ffn(z, p["mlp"], model,
                          dtype=_low(dtype, only, "experts")), mixed


_BLOCK_PROGRAMS: dict = {}


def _kind(model: dict, layer: int, dtype, only) -> tuple:
    """What a compiled block program is kept under: the model, the
    layer's kind and the rounding."""
    rope = model.get("rope_layout") or ()
    return (repr(sorted(model.items())), is_conv(model, layer),
            layer < model.get("dense_layers", 0),
            bool(rope and rope[layer % len(rope)]), dtype, only)


def _block_program(model: dict, layer: int, dtype, only):
    """:func:`block` for one ``model``, compiled ONCE for every layer of
    the same kind (mixer, dense or routed feed-forward, rotary or not: a
    layer's parameters are arguments). Called under an outer ``jax.jit``
    or ``jax.grad`` it is inlined there; called eagerly, as the
    benchmark's driver calls :func:`hidden` at 8,192 tokens, the five
    layers of ``lfm2-24b-a2b-ep8`` run three executables, not one program
    over all five (which a small compile cache evicts: PERF.md section
    7)."""
    only = tuple(only) if isinstance(only, list) else only
    key = _kind(model, layer, dtype, only)
    if key not in _BLOCK_PROGRAMS:
        fields = dict(model)
        _BLOCK_PROGRAMS[key] = jax.jit(lambda x, p: block(
            x, p, fields, layer, dtype=dtype, only=only))
    return _BLOCK_PROGRAMS[key]


def _block_vjp_program(model: dict, layer: int, dtype, only):
    """``(x, p, dy) -> (dx, dp)``: :func:`block`'s stream output pulled
    back, its forward taken again inside (a block's input is all that is
    kept between blocks), compiled once a kind as
    :func:`_block_program`."""
    only = tuple(only) if isinstance(only, list) else only
    key = ("vjp",) + _kind(model, layer, dtype, only)
    if key not in _BLOCK_PROGRAMS:
        fields = dict(model)

        def pull(x, p, dy):
            _, back = jax.vjp(lambda x, p: block(
                x, p, fields, layer, dtype=dtype, only=only)[0], x, p)
            return back(dy)

        _BLOCK_PROGRAMS[key] = jax.jit(pull)
    return _BLOCK_PROGRAMS[key]


def hidden(params, tokens, model: dict, *, dtype=None, only=None,
           mixers=()):
    """``(final-norm hidden states [B, T, D], {layer: the mixer's output
    [B, T, D]} for the layers in ``mixers``)``, float32."""
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        eps, layers = model["ln_epsilon"], model["num_layers"]
        table = backbone["token_embedding"]["embedding"]

        def one(ids):
            x, kept = table[ids], {}
            for layer in range(layers):
                x, mixed = _block_program(model, layer, dtype, only)(
                    x, backbone[f"encoder_block_{layer}"])
                if layer in mixers:
                    kept[layer] = mixed
            return rms_norm(x, backbone["encoder_norm"]["scale"], eps), kept

        rows = [one(ids) for ids in tokens]
        return (jnp.stack([r[0] for r in rows]),
                {layer: jnp.stack([r[1][layer] for r in rows])
                 for layer in mixers})


def logits(params, hid, *, dtype=None):
    """``hid [..., D]`` through the head tied to the token embedding,
    float32."""
    table = jnp.asarray(params["backbone"]["token_embedding"]["embedding"],
                        jnp.float32)
    return _mm(hid, table, "...d,vd->...v", dtype)


def _nll_sum(table, hid, targets, *, dtype=None, chunk: int = LOGIT_CHUNK):
    """The summed next-token cross entropy of ``hid [N, D]`` (final-norm
    hidden states) through the head tied to ``table``, ``chunk``
    positions' logits at a time."""
    total = 0.0
    for lo in range(0, hid.shape[0], chunk):
        lg = _mm(hid[lo:lo + chunk], table, "td,vd->tv", dtype)
        total = total + jnp.sum(
            jax.nn.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, targets[lo:lo + chunk, None], 1)[:, 0])
    return total


def loss(params, tokens, labels, model: dict, *, dtype=None, only=None,
         chunk: int = LOGIT_CHUNK):
    """The mean next-token cross entropy over every position."""
    hid = hidden(params, tokens, model, dtype=dtype, only=only)[0]
    table = jnp.asarray(params["backbone"]["token_embedding"]["embedding"],
                        jnp.float32)
    flat, targets = hid.reshape(-1, hid.shape[-1]), labels.reshape(-1)
    return _nll_sum(table, flat, targets, dtype=_low(dtype, only, "head"),
                    chunk=chunk) / flat.shape[0]


def _head_vjp_program(model: dict, dtype, only):
    """``(x, scale, table, targets, weight) -> (weight x the summed
    cross entropy, its gradients in x, the final norm's scale and the
    table)`` for one sequence's last block output ``x [T, D]``."""
    key = ("head", repr(sorted(model.items())), dtype, only)
    if key not in _BLOCK_PROGRAMS:
        eps, d_head = model["ln_epsilon"], _low(dtype, only, "head")

        def pull(x, scale, table, targets, weight):
            total, back = jax.vjp(lambda x, scale, table: _nll_sum(
                table, rms_norm(x, scale, eps), targets, dtype=d_head),
                x, scale, table)
            return (weight * total,) + back(weight)

        _BLOCK_PROGRAMS[key] = jax.jit(pull)
    return _BLOCK_PROGRAMS[key]


@jax.jit
def _lookup_grad(d_table, ids, dx):
    return d_table.at[ids].add(dx)


def gradients(params, tokens, labels, model: dict, *, dtype=None,
              only=None):
    """``(loss, gradients)`` of :func:`loss` at the size of one block's
    work: each sequence's forward keeps only the blocks' inputs, and its
    backward takes each block's forward again inside that block's
    ``jax.vjp`` (:func:`_block_vjp_program`), the head's chunks the same
    way; the table's gradient is the head's plus the lookup's. The same
    mathematics as ``jax.grad(loss)``, at a size that holds three
    sequences of 8,192 tokens on one chip. The tree is the parameters'
    (float32; the expert bias's 0)."""
    assert set(params) == {"backbone"}, sorted(params)
    only = tuple(only) if isinstance(only, list) else only
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        layers = model["num_layers"]
        table = backbone["token_embedding"]["embedding"]
        scale = backbone["encoder_norm"]["scale"]
        grads = jax.tree.map(jnp.zeros_like, backbone)
        weight = jnp.float32(1.0 / np.size(tokens))
        total = 0.0
        for ids, targets in zip(tokens, labels):
            ids = jnp.asarray(ids)
            xs = [table[ids]]
            for layer in range(layers):
                xs.append(_block_program(model, layer, dtype, only)(
                    xs[-1], backbone[f"encoder_block_{layer}"])[0])
            part, dx, d_scale, d_table = _head_vjp_program(
                model, dtype, only)(xs.pop(), scale, table,
                                    jnp.asarray(targets), weight)
            total = total + part
            grads["encoder_norm"]["scale"] += d_scale
            for layer in reversed(range(layers)):
                name = f"encoder_block_{layer}"
                dx, dp = _block_vjp_program(model, layer, dtype, only)(
                    xs.pop(), backbone[name], dx)
                grads[name] = jax.tree.map(jnp.add, grads[name], dp)
            grads["token_embedding"]["embedding"] = _lookup_grad(
                grads["token_embedding"]["embedding"] + d_table, ids, dx)
        return total, {"backbone": grads}


def agreement(got, want) -> dict:
    """How far ``got`` is from the reference ``want``, both in units of
    the reference's own spread (its standard deviation over all
    entries): ``rms`` and ``max``. The limit is set on ``rms``: a token
    whose fourth and fifth router scores lie closer than bfloat16
    resolves is routed otherwise by the program and owns ``max``, while
    a lower precision anywhere moves every entry and shows in ``rms``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    std = max(want.std(), 1e-12)
    diff = got - want
    return {"rms": float(np.sqrt(np.mean(diff * diff)) / std),
            "max": float(np.max(np.abs(diff)) / std)}
