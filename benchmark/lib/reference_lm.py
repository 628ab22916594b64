"""A plain float32 reference of the token model the program trains
(``--model lm``): forward, loss and, through ``jax.grad``, gradients.

``jax.numpy`` only, float32, ``jax.default_matmul_precision("highest")``;
no kernel, no sorting, no cache. It reads the program's parameter tree
and the ``model`` block of a configuration file (the program's config
fields) and shares no code with the program. Attention is computed from
an explicit visibility matrix, in blocks of queries so that 16,384
tokens fit one chip; the routed feed-forward is a dense loop over the
held experts with a ``where``. It takes ``experts_held`` /
``expert_offset`` and the vocabulary slice as the program does: what an
absent expert would add is left out, and logits and loss are over the
rows held.

**The layer**, from the source's ``config.json`` (SmallThinker-21BA3B-
Instruct, huggingface.co/PowerInfer). For block *l* and input ``x``
``[T, D]``:

* ``h = RMSNorm1(x)`` (eps 1e-6, scale only);
* router logits ``r = h W_r`` over all ``num_experts`` — *assumed*: the
  router reads this pre-attention normed ``h`` ("router placed before
  attention");
* ``q = h W_q`` (H heads of Dh), ``k = h W_k``, ``v = h W_v`` (Hkv heads)
  — *assumed*: no biases;
* where ``rope_layout[l] = 1``, rotary embedding on q and k (theta from
  the config, rotate-half over the whole head — *assumed* convention);
  none where 0;
* query head g attends key/value head ``g // (H / Hkv)``, scale
  ``Dh^-0.5``; key j is visible to query i iff ``j <= i`` and, where
  ``sliding_window_layout[l] = 1``, ``i - j < sliding_window``;
* ``x' = x + attn W_o``; ``u = RMSNorm2(x')``;
* ``S`` = the ``experts_per_token`` largest of ``r``, ``p =
  softmax(r[S])`` — *assumed*: the softmax is taken after the selection
  (``moe_primary_router_apply_softmax``; ``norm_topk_prob`` then divides
  by a sum that is 1);
* ``y = sum over e in S, e held, of p_e (relu(u W_gate,e) * (u W_up,e))
  W_down,e`` — *assumed* ReLU-gated ("sparse ReGLU");
* ``out = x' + y``.

Then a final RMSNorm, an untied head, and the mean next-token cross
entropy. *Assumed*: no auxiliary balance loss (the config gives no
coefficient), no dropout, documents packed without a boundary mask.
``described_as`` speaks of "secondary experts"; the config has no key
for them and none are built.

**Departures from the source**: the parameter layout is the program's
(``qkv`` is one matrix whose heads are q, then k, then v); only the
experts held and the vocabulary rows held exist; weights are random.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256     # queries whose [H, block, T] logits exist at a time
LOGIT_CHUNK = 2048    # positions whose [chunk, V] logits exist at a time


def _mm(a, b, spec: str, dtype=None):
    """``einsum`` in float32 at the highest precision; with ``dtype``
    both operands are first rounded to it (how a forward with narrower
    matmul inputs is told apart from this one)."""
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision="highest")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta):
    """``x [T, H, Dh]`` turned by its positions, rotate-half."""
    t, _, dh = x.shape
    freq = theta ** (-np.arange(0, dh, 2, dtype=np.float32) / dh)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    cos = np.concatenate([np.cos(angle), np.cos(angle)], -1)[:, None, :]
    sin = np.concatenate([np.sin(angle), np.sin(angle)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + turned * sin


def layer_kind(model: dict, layer: int):
    """``(rotary?, window or 0)`` of block ``layer``."""
    rope, win = model.get("rope_layout") or (), \
        model.get("sliding_window_layout") or ()
    return (bool(rope and rope[layer % len(rope)]),
            model["sliding_window"] if win and win[layer % len(win)] else 0)


def attention(q, k, v, window: int, block: int = QUERY_BLOCK, dtype=None):
    """Causal (``window`` = 0) or causal-window attention of one sequence:
    ``q [T, H, Dh]``, ``k`` / ``v`` ``[T, Hkv, Dh]`` -> ``[T, H, Dh]``."""
    t, h, dh = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    blocks = -(-t // block)
    q = jnp.pad(q, ((0, blocks * block - t), (0, 0), (0, 0)))
    cols = jnp.arange(t)[None, :]

    def one(args):
        q_rows, first = args
        rows = first + jnp.arange(block)[:, None]
        visible = cols <= rows
        if window:
            visible = visible & (rows - cols < window)
        visible = visible | (rows >= t)     # rows of padding: cut below
        s = _mm(q_rows, k, "qhd,khd->hqk", dtype) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return _mm(p, v, "hqk,khd->qhd", dtype)

    out = jax.lax.map(one, (q.reshape(blocks, block, h, dh),
                            jnp.arange(blocks) * block))
    return out.reshape(blocks * block, h, dh)[:t]


def routed_ffn(u, routed_from, p: dict, model: dict, *, dtype=None):
    """The held experts' part of the routed feed-forward for ``u [T,
    D]``, the router reading ``routed_from [T, D]``. ``p`` holds
    ``router/kernel [D, E]`` and ``gate`` / ``up`` ``[E_held, D, F]``,
    ``down [E_held, F, D]``: experts ``expert_offset .. + E_held``."""
    k = model["experts_per_token"]
    offset = model.get("expert_offset", 0)
    r = _mm(routed_from, p["router"]["kernel"], "td,de->te")
    top, ids = jax.lax.top_k(r, k)
    probs = jax.nn.softmax(top, axis=-1)
    y = jnp.zeros_like(u)
    for e in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(ids == e + offset, probs, 0.0), axis=-1)
        hidden = jax.nn.relu(_mm(u, p["gate"][e], "td,df->tf", dtype)) \
            * _mm(u, p["up"][e], "td,df->tf", dtype)
        y = y + weight[:, None] * _mm(hidden, p["down"][e], "tf,fd->td",
                                      dtype)
    return y


def block(x, p: dict, model: dict, layer: int, *, dtype=None, only=None):
    """One block for one sequence ``x [T, D]``. ``only`` confines
    ``dtype`` to the products one kernel of the program computes:
    ``"attn_core"`` (QK^T and PV) or ``"experts"`` (gate, up, down)."""
    d_core = dtype if only in (None, "attn_core") else None
    d_experts = dtype if only in (None, "experts") else None
    dtype = dtype if only is None else None
    eps = model["ln_epsilon"]
    hq, hkv = model["num_heads"], model["num_kv_heads"]
    rope, window = layer_kind(model, layer)
    h = rms_norm(x, p["msa"]["norm"]["scale"], eps)
    qkv = _mm(h, p["msa"]["qkv"]["kernel"], "td,dhe->the", dtype)
    q, k, v = qkv[:, :hq], qkv[:, hq:hq + hkv], qkv[:, hq + hkv:]
    if rope:
        q, k = rotary(q, model["rope_theta"]), rotary(k, model["rope_theta"])
    attn = attention(q, k, v, window, dtype=d_core)
    x = x + _mm(attn, p["msa"]["out"]["kernel"], "the,hed->td", dtype)
    u = rms_norm(x, p["mlp"]["norm"]["scale"], eps)
    return x + routed_ffn(u, h, p["mlp"], model, dtype=d_experts)


def hidden(params, tokens, model: dict, *, dtype=None, only=None):
    """Final-norm hidden states ``[B, T, D]`` float32 of token ids ``[B,
    T]`` under the program's parameter tree (``dtype``, ``only``: see
    :func:`block`)."""
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])

        def one(ids):
            x = backbone["token_embedding"]["embedding"][ids]
            for layer in range(model["num_layers"]):
                x = block(x, backbone[f"encoder_block_{layer}"], model,
                          layer, dtype=dtype, only=only)
            return rms_norm(x, backbone["encoder_norm"]["scale"],
                            model["ln_epsilon"])

        return jnp.stack([one(ids) for ids in tokens])


def logits(params, hid, *, dtype=None):
    """``hid [..., D]`` through the untied head, float32."""
    return _mm(hid, jnp.asarray(params["head"]["kernel"], jnp.float32),
               "...d,dv->...v", dtype)


def forward(params, tokens, model: dict, *, dtype=None):
    """Logits ``[B, T, V]`` over the vocabulary rows held."""
    return logits(params, hidden(params, tokens, model, dtype=dtype),
                  dtype=dtype)


def loss(params, tokens, labels, model: dict, *, dtype=None,
         chunk: int = LOGIT_CHUNK):
    """Mean next-token cross entropy over every position, the logits
    taken ``chunk`` positions at a time."""
    hid = hidden(params, tokens, model, dtype=dtype)
    hid = hid.reshape(-1, hid.shape[-1])
    flat = labels.reshape(-1)
    total = 0.0
    for lo in range(0, hid.shape[0], chunk):
        lg = logits(params, hid[lo:lo + chunk], dtype=dtype)
        total = total + jnp.sum(
            jax.nn.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, flat[lo:lo + chunk, None], 1)[:, 0])
    return total / hid.shape[0]


def agreement(got, want) -> dict:
    """How far ``got`` is from the reference ``want``, both in units of
    the reference's own spread (its standard deviation over all
    entries): ``rms`` (root mean square difference) and ``max``. The
    limit is set on ``rms``: one token whose sixth and seventh router
    logits lie closer than bfloat16 resolves is routed otherwise by the
    program, moves that token's logits by a whole expert's share and so
    owns ``max``, while a lower precision anywhere moves every entry and
    shows in ``rms``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    std = max(want.std(), 1e-12)
    diff = got - want
    return {"rms": float(np.sqrt(np.mean(diff * diff)) / std),
            "max": float(np.max(np.abs(diff)) / std)}
