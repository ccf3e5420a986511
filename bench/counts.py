"""Work a dense GQA decoder requires, counted from its shapes.

These counts read the same whatever implements the step: they count what
the model's equations need, not what a kernel happens to do.  No padding is
counted, no recomputation, and attention only over the rows a token really
attends to (causal, its own row included).

Per token and per layer, with ``d`` the model width, ``H``/``Hkv`` the
query/KV heads of size ``hd`` and ``f`` the MLP width:

* matmuls: ``2 * (d*H*hd + 2*d*Hkv*hd + H*hd*d + 3*d*f)`` FLOPs;
* attention over a context of ``c`` rows: ``4 * H * hd * c`` FLOPs
  (scores and the weighted sum);
* the unembedding: ``2 * d * V`` FLOPs per decoded token, and once per
  prompt (only its last row's logits are needed).

Bytes are HBM traffic a step cannot avoid: the weights read once per step
(one decode scan step, or one prefill chunk), each context row's K and V
read once per step that attends to it, and each new row's K and V written.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    dtype_bytes: int = 2

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        return cls(cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                   cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                   cfg["vocab_size"], cfg["tie_embeddings"],
                   2 if cfg["dtype"] in ("bfloat16", "float16") else 4)

    # -- parameters ----------------------------------------------------------

    @property
    def layer_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff + 2 * d

    @property
    def layer_bytes(self) -> int:
        """Weights of every layer."""
        return self.layers * self.layer_params * self.dtype_bytes

    @property
    def head_bytes(self) -> int:
        """The final norm and the unembedding (the embedding rows a step
        gathers are a rounding error)."""
        return (self.d_model * self.vocab + self.d_model) * self.dtype_bytes

    @property
    def weight_bytes(self) -> int:
        """Weights one decode step reads."""
        return self.layer_bytes + self.head_bytes

    @property
    def kv_bytes_per_row(self) -> int:
        """K and V of one context row, all layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # -- FLOPs ----------------------------------------------------------------

    @property
    def matmul_flops(self) -> int:
        """Per token: every layer's projections and MLP."""
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.layers * 2 * (d * q + 2 * d * kv + q * d + 3 * d * self.d_ff)

    @property
    def head_flops(self) -> int:
        """One row's logits."""
        return 2 * self.d_model * self.vocab

    def attention_flops(self, context_rows: int) -> int:
        """Per token attending to ``context_rows`` rows, all layers."""
        return self.layers * 4 * self.heads * self.head_dim * context_rows


def _span_attention_rows(first: int, last: int) -> int:
    """Sum of context sizes ``r + 1`` over rows ``first..last-1``."""
    return (last * (last + 1) - first * (first + 1)) // 2


def prefill(s: Shapes, shared: int, n: int, chunk: int) -> tuple[int, int, int]:
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


def decode(s: Shapes, prompt: int, generated: int) -> tuple[int, int]:
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
