"""A plain float32 reference of the latent-attention token model the
program trains (``--preset glm-4.7-flash-ep8``): forward, both losses
and, through ``jax.grad``, gradients.

``jax.numpy`` only, float32, ``jax.default_matmul_precision("highest")``;
no kernel, no sorting, no cache, no recomputation. It reads the program's
parameter tree and the ``model`` block of a configuration file and shares
no code with the program (nor with ``reference_lm.py``: the few helpers
both need are written again here). Attention is computed from an explicit
visibility matrix, in blocks of queries so that 16,384 tokens fit one
chip; the routed feed-forward is a dense loop over the held experts with
a ``where``. It takes ``experts_held`` / ``expert_offset`` and the
vocabulary slice as the program does: what an absent expert would add is
left out, and logits and losses are over the rows held.

**The equations**, from the source's ``config.json`` (GLM-4.7-Flash,
huggingface.co/zai-org, ``glm4_moe_lite``) and, for the module,
DeepSeek-V3 section 2.2. ``h`` is the residual stream ``[T, D]``; every
norm is RMSNorm (scale only) at ``ln_epsilon``.

1. *Latent attention*, ``a = norm(h)``: ``c_q = norm_q(a W_qa)``; ``q =
   c_q W_qb`` -> H heads of ``[q_nope | q_rope]``. ``[c | k_r] = a
   W_kva``; ``c_kv = norm_kv(c)``; ``[k_nope | v]`` per head ``= c_kv
   W_kvb``. ``q_rope`` and the ONE ``k_r`` are turned by their positions
   (theta from the config, over all ``qk_rope_head_dim`` columns,
   rotate-half — *assumed* convention); head i reads ``q_i = [q_nope_i |
   rot(q_rope_i)]``, ``k_i = [k_nope_i | rot(k_r)]``. ``o_i =
   softmax(q_i k_i^T / sqrt(nope + rope) + causal) v_i``; ``h += concat(o)
   W_o``. No biases (*assumed* beyond ``attention_bias`` false).
2. *Feed-forward*, ``u = norm(h)``. A layer below ``dense_layers``: ``h +=
   (silu(u W_g) * (u W_u)) W_d``. Others: ``s = sigmoid(u W_r)``; the
   ``experts_per_token`` experts with the largest ``s + b`` (``b``: the
   correction bias, used for the selection only); weights ``w_e =
   router_scale * s_e / (sum of the selected s + 1e-20)``; ``h +=
   shared(u) + sum over e selected AND held of w_e expert_e(u)``.
3. *Final*: ``logits = norm_f(h_L) W_head``; main loss = mean cross
   entropy against the next token.
4. *Multi-token prediction*: ``x_i = W_eh [norm_e(E[t_{i+1}]) ;
   norm_h(h_L,i)]`` (``h_L`` before ``norm_f``; the order of the two
   halves *assumed*), one routed block as in 1-2 with weights of its own,
   ``norm_s``, the SAME ``W_head``; target ``t_{i+2}``; the last position
   has none and is left out of the mean. Objective ``main +
   mtp_loss_weight x module``.

**Departures from the source**: the parameter layout is the program's
(``kv_up`` one ``[r, H, nope + v]`` array, ``eh_proj`` one ``[2D, D]``);
only the experts held and the vocabulary rows held exist; the correction
bias is not updated between steps; weights are random.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256     # queries whose [H, block, T] logits exist at a time
LOGIT_CHUNK = 2048    # positions whose [chunk, V] logits exist at a time
FAMILIES = ("attn_core", "latent", "experts", "dense", "head")


def _mm(a, b, spec: str, dtype=None):
    """``einsum`` in float32 at the highest precision; with ``dtype``
    both operands are first rounded to it (how a forward with narrower
    matmul inputs is told apart from this one)."""
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision="highest")


def _low(dtype, only, family):
    """``dtype`` where the control rounds this product ``family`` (all of
    them where ``only`` is None), else None."""
    assert only is None or only in FAMILIES, only
    return dtype if only in (None, family) else None


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


def causal_attention(q, k, v, block: int = QUERY_BLOCK, dtype=None):
    """``q``, ``k`` ``[T, H, Dqk]``, ``v`` ``[T, H, Dv]`` -> ``[T, H,
    Dv]``: key j visible to query i iff ``j <= i``."""
    t, h, dqk = q.shape
    blocks = -(-t // block)
    q = jnp.pad(q, ((0, blocks * block - t), (0, 0), (0, 0)))
    cols = jnp.arange(t)[None, :]

    def one(args):
        q_rows, first = args
        rows = first + jnp.arange(block)[:, None]
        visible = (cols <= rows) | (rows >= t)   # rows of padding: cut below
        s = _mm(q_rows, k, "qhd,khd->hqk", dtype) * dqk ** -0.5
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return _mm(p, v, "hqk,khd->qhd", dtype)

    out = jax.lax.map(one, (q.reshape(blocks, block, h, dqk),
                            jnp.arange(blocks) * block))
    return out.reshape(blocks * block, h, v.shape[-1])[:t]


def latent_attention(a, p: dict, model: dict, *, dtype=None, only=None):
    """Equation 1 for the normed input ``a [T, D]``, before ``W_o``'s
    residual add: returns ``concat(o) W_o``."""
    eps, rank, nope = (model["ln_epsilon"], model["kv_lora_rank"],
                       model["qk_nope_head_dim"])
    d_lat, d_core = _low(dtype, only, "latent"), \
        _low(dtype, only, "attn_core")
    c_q = rms_norm(_mm(a, p["q_down"]["kernel"], "td,dr->tr", d_lat),
                   p["q_norm"]["scale"], eps)
    q = _mm(c_q, p["q_up"]["kernel"], "tr,rhe->the", d_lat)
    c = _mm(a, p["kv_down"]["kernel"], "td,dr->tr", d_lat)
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], eps)
    k_r = rotary(c[:, None, rank:], model["rope_theta"])       # ONE head
    kv = _mm(c_kv, p["kv_up"]["kernel"], "tr,rhe->the", d_lat)
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], model["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_r, kv.shape[1], axis=1)], -1)
    o = causal_attention(q, k, kv[..., nope:], dtype=d_core)
    return _mm(o, p["out"]["kernel"], "the,hed->td", d_lat)


def gated(u, p: dict, dtype=None):
    """``(silu(u W_g) * (u W_u)) W_d`` with ``p = {gate, up, down}``
    (each ``{kernel}``)."""
    hidden = jax.nn.silu(_mm(u, p["gate"]["kernel"], "td,df->tf", dtype)) \
        * _mm(u, p["up"]["kernel"], "td,df->tf", dtype)
    return _mm(hidden, p["down"]["kernel"], "tf,fd->td", dtype)


def route(u, p: dict, model: dict):
    """``(ids [T, k], weights [T, k])`` of equation 2's router."""
    s = jax.nn.sigmoid(_mm(u, p["router"]["kernel"], "td,de->te"))
    _, ids = jax.lax.top_k(s + p["router_bias"], model["experts_per_token"])
    picked = jnp.take_along_axis(s, ids, axis=-1)
    return ids, model["router_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed_ffn(u, p: dict, model: dict, *, dtype=None, offset=None,
               shared=True):
    """Equation 2's routed layer for ``u [T, D]``: the shared expert
    (``shared``) and the part of the experts that ``p`` holds, experts
    ``offset .. + E_held`` (``offset`` defaults to the model's)."""
    offset = model.get("expert_offset", 0) if offset is None else offset
    ids, weights = route(u, p, model)
    y = gated(u, p["shared"], dtype) if shared else jnp.zeros_like(u)
    for e in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        hidden = jax.nn.silu(_mm(u, p["gate"][e], "td,df->tf", dtype)) \
            * _mm(u, p["up"][e], "td,df->tf", dtype)
        y = y + weight[:, None] * _mm(hidden, p["down"][e], "tf,fd->td",
                                      dtype)
    return y


def block(x, p: dict, model: dict, layer: int, *, dtype=None, only=None):
    """One block for one sequence ``x [T, D]``. ``only`` confines
    ``dtype`` to one family of products (:data:`FAMILIES`)."""
    eps = model["ln_epsilon"]
    x = x + latent_attention(rms_norm(x, p["msa"]["norm"]["scale"], eps),
                             p["msa"], model, dtype=dtype, only=only)
    u = rms_norm(x, p["mlp"]["norm"]["scale"], eps)
    if layer < model.get("dense_layers", 0):
        return x + gated(u, p["mlp"]["dense"], _low(dtype, only, "dense"))
    return x + routed_ffn(u, p["mlp"], model,
                          dtype=_low(dtype, only, "experts"))


def hidden(params, tokens, labels, model: dict, *, dtype=None, only=None):
    """``(main [B, T, D], module [B, T, D])`` float32: the final-norm
    hidden states of the main model and, with ``labels`` (each
    position's next token), of the multi-token-prediction module (None
    without)."""
    with jax.default_matmul_precision("highest"):
        backbone = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
        eps, layers = model["ln_epsilon"], model["num_layers"]
        table = backbone["token_embedding"]["embedding"]

        def one(ids, ahead):
            x = table[ids]
            for layer in range(layers):
                x = block(x, backbone[f"encoder_block_{layer}"], model,
                          layer, dtype=dtype, only=only)
            main = rms_norm(x, backbone["encoder_norm"]["scale"], eps)
            if ahead is None:
                return main, None
            m = backbone["mtp"]
            merged = jnp.concatenate(
                [rms_norm(table[ahead], m["norm_e"]["scale"], eps),
                 rms_norm(x, m["norm_h"]["scale"], eps)], axis=-1)
            y = _mm(merged, m["eh_proj"]["kernel"], "te,ed->td",
                    _low(dtype, only, "latent"))
            y = block(y, m[f"encoder_block_{layers}"], model, layers,
                      dtype=dtype, only=only)
            return main, rms_norm(y, m["norm_s"]["scale"], eps)

        rows = [one(ids, None if labels is None else labels[i])
                for i, ids in enumerate(tokens)]
        main = jnp.stack([r[0] for r in rows])
        return main, (None if labels is None
                      else jnp.stack([r[1] for r in rows]))


def logits(params, hid, *, dtype=None):
    """``hid [..., D]`` through the untied head, float32."""
    return _mm(hid, jnp.asarray(params["head"]["kernel"], jnp.float32),
               "...d,dv->...v", dtype)


def _nll_sum(params, hid, targets, dtype, chunk):
    """Sum of the cross entropies of ``hid [N, D]`` against ``targets
    [N]``, the logits taken ``chunk`` positions at a time."""
    total = 0.0
    for lo in range(0, hid.shape[0], chunk):
        lg = logits(params, hid[lo:lo + chunk], dtype=dtype)
        total = total + jnp.sum(
            jax.nn.logsumexp(lg, axis=-1)
            - jnp.take_along_axis(lg, targets[lo:lo + chunk, None], 1)[:, 0])
    return total


def losses(params, tokens, labels, model: dict, *, dtype=None, only=None,
           chunk: int = LOGIT_CHUNK):
    """``(main, module)``: the mean next-token cross entropy over every
    position, and the module's against the token after the next over
    every position of a sequence but its last."""
    main, module = hidden(params, tokens, labels, model, dtype=dtype,
                          only=only)
    d_head = _low(dtype, only, "head")
    b, t, d = main.shape
    main_loss = _nll_sum(params, main.reshape(-1, d), labels.reshape(-1),
                         d_head, chunk) / (b * t)
    module_loss = _nll_sum(params, module[:, :-1].reshape(-1, d),
                           labels[:, 1:].reshape(-1), d_head,
                           chunk) / (b * (t - 1))
    return main_loss, module_loss


def loss(params, tokens, labels, model: dict, **kw):
    """The objective: ``main + mtp_loss_weight x module``."""
    main_loss, module_loss = losses(params, tokens, labels, model, **kw)
    return main_loss + model["mtp_loss_weight"] * module_loss


def forward(params, tokens, model: dict, *, dtype=None):
    """Main logits ``[B, T, V]`` over the vocabulary rows held."""
    return logits(params, hidden(params, tokens, None, model,
                                 dtype=dtype)[0], dtype=dtype)


def agreement(got, want) -> dict:
    """How far ``got`` is from the reference ``want``, both in units of
    the reference's own spread (its standard deviation over all
    entries): ``rms`` and ``max``. The limit is set on ``rms``: one token
    whose fourth and fifth router scores lie closer than bfloat16
    resolves is routed otherwise by the program and owns ``max``, while a
    lower precision anywhere moves every entry and shows in ``rms``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    std = max(want.std(), 1e-12)
    diff = got - want
    return {"rms": float(np.sqrt(np.mean(diff * diff)) / std),
            "max": float(np.max(np.abs(diff)) / std)}
