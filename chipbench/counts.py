"""Work the algorithm needs, as functions of shapes: the model FLOPs of a
round and the least HBM bytes of each round kernel.  They count what the
algorithm requires, not what an implementation happens to do, so a change
that removes copies or fuses kernels moves the measured share and not the
count."""
from __future__ import annotations


def lm_params(cfg: dict) -> int:
    """Parameters of a dense decoder with tied embeddings: the embedding
    (which is also the output head) plus, per layer, the q/k/v/o
    projections and a gated MLP of three d x d_ff matrices."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    attn = d * cfg["n_heads"] * cfg["head_dim"] * 3 + cfg["n_heads"] * cfg["head_dim"] * d
    return v * d + cfg["n_layers"] * (attn + 3 * d * f)


def lm_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Forward and backward FLOPs of ``sequences`` causal sequences:
    6 x parameters x tokens (the tied head counted once, the embedding
    lookup not at all), plus the attention score and value products.  Those
    take 4 S^2 (H hd) per layer and sequence forward without a mask; the
    causal mask halves that to 2 S^2 (H hd), and the backward pass doubles
    it, so 6 S^2 (H hd) per layer and sequence in all.  Recomputation
    (rematerialisation) is not counted."""
    tokens = sequences * seq_len
    dense = 6.0 * lm_params(cfg) * tokens
    attn = 6.0 * seq_len ** 2 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return dense + attn * sequences


def mlr_params(cfg: dict) -> int:
    return cfg["n_features"] * cfg["n_classes"] + cfg["n_classes"]


def mlr_flops(cfg: dict, samples: int) -> float:
    """Forward logits x W (2 F C per sample) and the weight gradient
    x^T err (2 F C per sample); no input gradient is needed."""
    return 4.0 * cfg["n_features"] * cfg["n_classes"] * samples


def fused_update_bytes(m: int, n: int, itemsize: int) -> int:
    """Eq. (20) over m client rows of n parameters: read x, g and lam once,
    the server row x_s once, and write x once."""
    return (4 * m + 1) * n * itemsize


def round_tail_bytes(m: int, n: int, itemsize: int) -> int:
    """The uplink pass u = xbar - (rho (x_s - xbar) - lam) / rho: read xbar
    and lam once, x_s once, and write u once."""
    return (3 * m + 1) * n * itemsize
