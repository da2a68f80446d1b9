"""Operations and bytes the algorithms need, from static shapes.

Copied in arithmetic from ``bench.model_flops_per_token`` (the one sound
piece of the old bench); kept here so no later PR can change the yardstick.
Matmul FLOPs only; backward counts twice the forward; recomputation never
counts.
"""

from __future__ import annotations


def bert_train_flops_per_token(*, hidden: int, intermediate: int, layers: int,
                               vocab: int, seq_len: int, mlm_k: int) -> float:
    """Forward + backward matmul FLOPs per input token of BERT pretraining
    with the MLM head evaluated at ``mlm_k`` of ``seq_len`` positions."""
    per_layer = 8 * hidden * hidden + 4 * seq_len * hidden \
        + 4 * hidden * intermediate
    head = (2 * hidden * hidden + 2 * hidden * vocab) * (mlm_k / seq_len)
    return 3.0 * (layers * per_layer + head)


def flash_train_flops(*, batch: int, heads: int, seq_len: int, head_dim: int,
                      layers: int) -> float:
    """Matmul FLOPs of non-causal flash attention, forward (QK^T and PV:
    4*s*s*d per head) and backward (dQ, dK, dV and dP: 8*s*s*d; the kernel's
    recompute of QK^T is not counted), over all layers of one step."""
    per_head = 12 * seq_len * seq_len * head_dim
    return float(batch * heads * layers * per_head)


def flash_train_bytes(*, batch: int, heads: int, seq_len: int, head_dim: int,
                      layers: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q,k,v and writes o;
    backward reads q,k,v,o,do and writes dq,dk,dv (lse and delta rows are
    left out: they are 1/head_dim of a tensor)."""
    tensor = batch * heads * seq_len * head_dim * itemsize
    return float(layers * 12 * tensor)


def gpt_param_count(*, hidden: int, layers: int, vocab: int,
                    positions: int) -> int:
    """Parameters of a GPT-2 block stack with tied embeddings: per layer
    12*e*e weights + 13*e biases and norms; embeddings; the final norm."""
    per_layer = 12 * hidden * hidden + 13 * hidden
    return layers * per_layer + (vocab + positions) * hidden + 2 * hidden


def gpt_forward_flops_per_token(*, hidden: int, layers: int,
                                vocab: int) -> float:
    """2 x the parameters a token's forward pass multiplies with (block
    matmuls and the tied head; attention over the context is left out, so
    the MFU built on this is a lower bound of the work done)."""
    return 2.0 * (layers * 12 * hidden * hidden + vocab * hidden)


def tree_bytes(tree) -> int:
    """Bytes of every array leaf of ``tree`` as it is held."""
    import jax

    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))
