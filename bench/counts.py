"""Work a step requires, counted from the shapes of the cell's
architecture: the object that ``shapes(cfg)`` of ``bench/archs/<a>.py``
returns, with ``layer_bytes``, ``head_bytes``, ``weight_bytes``,
``kv_bytes_per_row``, ``matmul_flops``, ``head_flops`` and
``attention_flops(rows)``.

These counts read the same whatever implements the step: they count what
the model's equations need, not what a kernel happens to do.  No padding is
counted, no recomputation, and attention only over the rows a token really
attends to (causal, its own row included).  The unembedding is counted per
decoded token, and once per prompt (only its last row's logits are
needed).

Bytes are HBM traffic a step cannot avoid: the weights read once per step
(one decode scan step, or one prefill chunk), each context row's K and V
read once per step that attends to it, and each new row's K and V written.
"""

from __future__ import annotations


def _span_attention_rows(first: int, last: int) -> int:
    """Sum of context sizes ``r + 1`` over rows ``first..last-1``."""
    return (last * (last + 1) - first * (first + 1)) // 2


def prefill(s, shared: int, n: int, chunk: int) -> tuple[int, int, int]:
    """``(flops, bytes, chunks)`` to prefill rows ``[shared, n)`` of a prompt
    of ``n`` tokens whose first ``shared`` rows are already cached, in
    chunks of at most ``chunk`` rows."""
    rows = n - shared
    chunks = -(-rows // chunk)
    flops = (rows * s.matmul_flops + s.head_flops
             + s.attention_flops(1) * _span_attention_rows(shared, n))
    # each chunk reads the layers' weights and the K/V of the rows before
    # it, and writes its own rows' K/V; the last row's logits read the head
    kv_read = sum(shared + i * chunk for i in range(chunks))
    nbytes = (chunks * s.layer_bytes + s.head_bytes
              + (kv_read + rows) * s.kv_bytes_per_row)
    return flops, nbytes, chunks


def decode(s, prompt: int, generated: int) -> tuple[int, int]:
    """``(flops, kv_bytes)`` of the ``generated - 1`` decode steps that
    follow a prompt of ``prompt`` tokens (the first token comes from the
    prefill).  The weights a decode step reads are shared by the whole
    batch and counted per step by the caller."""
    steps = generated - 1
    first, last = prompt, prompt + steps          # rows the steps write
    flops = (steps * (s.matmul_flops + s.head_flops)
             + s.attention_flops(1) * _span_attention_rows(first, last))
    ctx = _span_attention_rows(first, last)       # rows read, own row included
    return flops, (ctx + steps) * s.kv_bytes_per_row
