"""What the benchmark knows about the attention core of a latent-
attention model in training, from shapes alone.

**The core** (scope ``attn_core``: from q, k ``[T, H, Dqk]`` and v ``[T,
H, Dv]`` to o, whatever computes it), per block and sequence; k and v
are whole per head (the absorbed form is a decode matter):

* FLOPs: 6 GEMMs over the VISIBLE query-key pairs only (forward q k^T
  and p v; backward dv, dp, dq, dk), ``2 * pairs * H * 256`` each (the
  query/key width ``qk_nope + qk_rope`` and the value width are both
  256); a causal block has ``T (T + 1) / 2`` pairs. Rebuilding the logits
  in the backward pass is recomputation and is not counted.
* bytes: forward q, k, v read and o written; backward q, k, v, o, do
  read and dq, dk, dv written, in the compute dtype. Every head has its
  own ``qk_nope`` key columns and its own values, but the key's
  ``qk_rope`` columns are ONE head that all H share: they are counted
  once where k is read and once where dk is written, not H times. A
  program that lays them out H times (today's does) is that much
  further from its roofline. The ``[T, T]`` logits are not counted.
"""

from __future__ import annotations

from . import flops_mla


def attention_core_cost(model: dict, seq_len: int, sequences: int, *,
                        act_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes of the attention core of one train step on
    one chip (every block that runs, forward and backward)."""
    h = model["num_heads"]
    nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    n = flops_mla.blocks(model)
    flops = n * 6 * 2.0 * flops_mla.visible_pairs(seq_len) * h * (nope + rope)
    q_like = h * (nope + rope)            # q, dq
    o_like = h * dv                       # o, do, v, dv
    k_like = h * nope + rope              # k, dk: the rotary head once
    # forward q, k, v, o; backward q, k, v, o, do, dq, dk, dv
    columns = (q_like + k_like + 2 * o_like) \
        + (2 * q_like + 2 * k_like + 4 * o_like)
    bytes_ = n * seq_len * columns * act_bytes
    return {"flops": sequences * flops, "bytes": float(sequences * bytes_)}
