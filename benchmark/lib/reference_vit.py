"""Plain reference: the ViT forward pass in float32 ``jax.numpy``.

Follows arXiv:2010.11929 section 3.1 and appendix B: patches projected
linearly, a class token prepended, learned position embeddings added,
then L pre-norm blocks ``x + MSA(LN(x))``, ``x + MLP(LN(x))`` with an
exact (erf) GELU, a final LayerNorm, and a linear head on the class
token. No Flax, no kernels, no dropout (eval mode), and
``jax.default_matmul_precision("highest")`` so that a float32 matmul on
the TPU is a float32 matmul.

It reads the program's parameter tree by the program's names (the
mapping is the only thing taken from the program):
``backbone/patch_embedding/{patch_conv,cls_token,pos_embedding}``,
``backbone/encoder_block_<i>/{msa/{norm,qkv,out},mlp/{norm,fc1,fc2}}``,
``backbone/encoder_norm``, ``head``. Departures from the paper: none in
the mathematics; the patch projection is written as the unfold + matmul
that a stride-P convolution is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _block(x, p, eps):
    y = _ln(x, p["msa"]["norm"], eps)
    qkv = jnp.einsum("btd,dchk->btchk", y, p["msa"]["qkv"]["kernel"]) \
        + p["msa"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]      # [B,T,H,Dh]
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(q.shape[-1])
    attn = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(logits, -1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", attn, p["msa"]["out"]["kernel"]) \
        + p["msa"]["out"]["bias"]
    y = _ln(x, p["mlp"]["norm"], eps)
    h = y @ p["mlp"]["fc1"]["kernel"] + p["mlp"]["fc1"]["bias"]
    h = 0.5 * h * (1.0 + jax.lax.erf(h / np.sqrt(2.0)))
    return x + h @ p["mlp"]["fc2"]["kernel"] + p["mlp"]["fc2"]["bias"]


def forward(params, images, *, patch_size: int, ln_epsilon: float = 1e-6,
            pool: str = "cls"):
    """Float32 logits ``[B, classes]`` for float images ``[B,H,W,C]``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        bb, pe = params["backbone"], params["backbone"]["patch_embedding"]
        x = jnp.asarray(images, jnp.float32)
        b, hgt, wid, c = x.shape
        p, n = patch_size, hgt // patch_size
        x = x.reshape(b, n, p, n, p, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, n * n, p * p * c)
        x = x @ pe["patch_conv"]["kernel"].reshape(p * p * c, -1) \
            + pe["patch_conv"]["bias"]
        if pool == "cls":
            cls = jnp.broadcast_to(pe["cls_token"], (b, 1, x.shape[-1]))
            x = jnp.concatenate([cls, x], axis=1)
        x = x + pe["pos_embedding"]
        i = 0
        while f"encoder_block_{i}" in bb:
            x = _block(x, bb[f"encoder_block_{i}"], ln_epsilon)
            i += 1
        x = _ln(x, bb["encoder_norm"], ln_epsilon)
        pooled = x[:, 0] if pool == "cls" else x.mean(axis=1)
        return pooled @ params["head"]["kernel"] + params["head"]["bias"]


def agreement(got, want) -> float:
    """Largest absolute difference of two logit arrays, in units of the
    reference's own spread (its standard deviation over all entries).
    Logits, not classes: with random weights the largest logit changes
    on rounding."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(want.std(), 1e-12))


def log_rows(probs) -> np.ndarray:
    """Softmax rows as centred log-probabilities, i.e. logits up to the
    row's constant: how served ``probs`` rows are compared with the
    reference's logits."""
    lp = np.log(np.maximum(np.asarray(probs, np.float64), 1e-300))
    return lp - lp.mean(-1, keepdims=True)


# bf16 compute against the float32 reference, random weights, 12-24
# layers. Set from what the chip measured (PERF.md section 4: 0.026 to
# 0.039 in every run of every cell): the program's bf16 forward sits
# under half of this, and a forward whose matmul inputs are rounded to 8
# bits (fp8 e4m3, 3 mantissa bits against bf16's 7, so ~16x the rounding
# error) lands far above it.
TOLERANCE = 0.08
