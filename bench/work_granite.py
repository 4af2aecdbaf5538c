"""Operations and bytes of the granite cell's backbone, from the model's
definition (``cfg`` is the configuration file's dict, as cut).

Operations are the matrix products' multiply-adds, two each; element-wise
work (norms, activations, the conv's taps aside) is left out.  Per token
and layer:

* Mamba-2: the in-projection ``D -> 2*di + 2*G*N + H``, the depthwise
  conv ``K*(di + 2*G*N)``, the out-projection ``di -> D``, and SSD as the
  chunked algorithm at the config's chunk ``Q``: per chunk ``C B^T``
  (``Q^2 N G``), its masked product with ``x`` (``Q^2 H P``), the chunk
  states and their read-out (``Q H P N`` each);
* attention: the q/k/v/o projections, and per sequence the causal
  triangle of scores and weighted values, ``2 * H * hd * L(L+1)/2``;
* every layer: the router over the published experts, the held experts'
  share of the top-k (``k * n / E`` experts per token) and the shared
  expert, each SwiGLU ``3 * D * F``;
* the 16 logit columns the head's features read.

Bytes are the weights (bf16) read once per step, each token's
embedding row, the int32 tokens and lengths in and the packed answer
out.
"""

from __future__ import annotations

from bench.granite_weights import dims

FEATS = 16


def _macs_per_token(cfg: dict, kind: str) -> float:
    d = dims(cfg)
    D, di, H, P, N, G = d["D"], d["di"], d["H"], d["P"], d["N"], d["G"]
    Q = cfg["mamba_chunk_size"]
    if kind == "mamba":
        mixer = (D * (2 * di + 2 * G * N + H) + d["K"] * d["conv"]
                 + di * D + Q * N * G + Q * H * P + 2 * H * P * N)
    else:
        mixer = 2 * D * d["heads"] * d["hd"] + 2 * D * d["kv"] * d["hd"]
    routed = cfg["num_experts_per_tok"] * d["n"] / d["E"]
    ffn = D * d["E"] + routed * 3 * D * d["F"] + 3 * D * d["Fs"]
    return mixer + ffn


def sequence_flops(cfg: dict, length: int) -> float:
    """Operations to pool one sequence of ``length`` tokens."""
    d = dims(cfg)
    per_token = sum(_macs_per_token(cfg, k) for k in cfg["layer_types"])
    per_token += d["D"] * FEATS
    n_attn = sum(k == "attention" for k in cfg["layer_types"])
    causal = n_attn * 2 * d["heads"] * d["hd"] * length * (length + 1) / 2
    return 2.0 * (per_token * length + causal)


def weight_params(cfg: dict) -> int:
    """Parameters a step reads: every layer, the final norm and the 16
    embedding rows of the logit columns (the gathered rows are counted
    per token)."""
    d = dims(cfg)
    D, di, H = d["D"], d["di"], d["H"]
    mamba = (D * (2 * di + 2 * d["G"] * d["N"] + H) + d["K"] * d["conv"]
             + d["conv"] + 3 * H + di + di * D)
    attn = 2 * D * d["heads"] * d["hd"] + 2 * D * d["kv"] * d["hd"]
    ffn = D * d["E"] + d["n"] * 3 * D * d["F"] + 3 * D * d["Fs"] + 2 * D
    total = sum((mamba if k == "mamba" else attn) + ffn
                for k in cfg["layer_types"])
    return total + D + FEATS * D


def step_bytes(cfg: dict, batch: int, length: int) -> float:
    head = cfg["head"]
    answer = (FEATS + head["classes"] + 1) * 4
    return (2.0 * weight_params(cfg)
            + batch * length * (2 * cfg["hidden_size"] + 4)
            + batch * (4 + answer))


def step_flops(cfg: dict, batch: int, length: int) -> float:
    """Operations of one padded step: ``batch`` sequences of ``length``."""
    return batch * sequence_flops(cfg, length)


def least_step_s(cfg: dict, batch: int, length: int, pk: dict) -> float:
    """The larger of the step's operations over the bf16 peak and its
    bytes over HBM bandwidth."""
    return max(step_flops(cfg, batch, length) / pk["bf16_flops_per_s"],
               step_bytes(cfg, batch, length) / pk["hbm_bytes_per_s"])
