"""Operations and bytes of the Kimi Linear cell's backbone, from the
model's definition (``cfg`` is the configuration file's dict, as cut).

Operations are the matrix products' multiply-adds, two each; element-wise
work (norms, activations, gates' non-linearities, the conv's taps aside)
is left out.  Per token and layer:

* KDA: the q/k/v projections ``D -> H*d`` each, their depthwise convs
  ``K*H*d`` each, the decay and output gates ``D -> r -> H*d`` each, beta
  ``D -> H``, the out-projection ``H*d -> D``, and the recurrence's own
  products, ``3*d*d`` per head (``S'^T k``, ``k u^T``, ``S^T q``): the
  fewest the recurrence needs, whatever chunking computes it;
* MLA: ``q = W_q x`` (``D -> H*(dn+dr)``), ``W_kva`` (``D -> R+dr``),
  ``W_kvb`` (``R -> H*(dn+dv)``), ``W_o`` (``H*dv -> D``), and per
  sequence the causal triangle of scores and weighted values,
  ``H*(dn+dr+dv) * L(L+1)/2``;
* feed-forward: the leading dense SwiGLU ``3*D*Fd``; elsewhere the router
  over the published experts, the held experts' share of the top-k
  (``k*n/E`` experts per token) and the shared expert, each SwiGLU
  ``3*D*F``;
* the 16 logit columns the head's features read.

Bytes are the weights (bf16) read once per step, each token's embedding
row, the int32 tokens and lengths in and the packed answer out.  The KDA
recurrence alone (:func:`kda_least_step_s`) reads q, k, v (bf16), its
decay g (float32) and beta, and writes o (bf16).
"""

from __future__ import annotations

from bench.kimi_weights import dims, layer_kinds

FEATS = 16


def _kda_recurrence_macs(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["H"] * d["d"] * d["d"]


def _macs_per_token(cfg: dict, kind: str, dense: bool) -> float:
    d = dims(cfg)
    D, Hd, r = d["D"], d["H"] * d["d"], d["r"]
    if kind == "kda":
        mixer = (3 * D * Hd + 3 * d["K"] * Hd + 2 * (D * r + r * Hd)
                 + D * d["H"] + Hd * D + _kda_recurrence_macs(cfg))
    else:
        h, R = d["heads"], d["R"]
        mixer = (D * h * (d["dn"] + d["dr"]) + D * (R + d["dr"])
                 + R * h * (d["dn"] + d["dv"]) + h * d["dv"] * D)
    if dense:
        return mixer + 3 * D * d["Fd"]
    routed = d["k"] * d["n"] / d["E"]
    return mixer + D * d["E"] + routed * 3 * D * d["F"] + 3 * D * d["Fs"]


def sequence_flops(cfg: dict, length: int) -> float:
    """Operations to pool one sequence of ``length`` tokens."""
    d = dims(cfg)
    kinds = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    per_token = sum(_macs_per_token(cfg, k, i < dense)
                    for i, k in enumerate(kinds))
    per_token += d["D"] * FEATS
    causal = (kinds.count("mla") * d["heads"]
              * (d["dn"] + d["dr"] + d["dv"]) * length * (length + 1) / 2)
    return 2.0 * (per_token * length + causal)


def weight_params(cfg: dict) -> int:
    """Parameters a step reads: every layer, the final norm and the 16
    lm_head columns of the features (the gathered embedding rows are
    counted per token)."""
    d = dims(cfg)
    D, H, Hd, r = d["D"], d["H"], d["H"] * d["d"], d["r"]
    kda = (3 * D * Hd + 3 * d["K"] * Hd + 2 * (D * r + r * Hd) + 2 * Hd
           + H + D * H + d["d"] + Hd * D)
    h, R = d["heads"], d["R"]
    mla = (D * h * (d["dn"] + d["dr"]) + D * (R + d["dr"]) + R
           + R * h * (d["dn"] + d["dv"]) + h * d["dv"] * D)
    moe = D * d["E"] + d["E"] + d["n"] * 3 * D * d["F"] + 3 * D * d["Fs"]
    total = 0
    for i, kind in enumerate(layer_kinds(cfg)):
        total += (kda if kind == "kda" else mla) + 2 * D
        total += (3 * D * d["Fd"] if i < cfg["first_k_dense_replace"]
                  else moe)
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


def kda_least_step_s(cfg: dict, batch: int, length: int, pk: dict) -> float:
    """The least time of one step's KDA recurrences (every KDA layer, on
    ``batch`` sequences of ``length``): their operations over the bf16
    peak or their bytes over HBM bandwidth, whichever is larger."""
    d = dims(cfg)
    tokens = batch * length * layer_kinds(cfg).count("kda")
    Hd = d["H"] * d["d"]
    flops = 2.0 * tokens * _kda_recurrence_macs(cfg)
    nbytes = tokens * (3 * 2 * Hd + 4 * Hd + 4 * d["H"] + 2 * Hd)
    return max(flops / pk["bf16_flops_per_s"],
               nbytes / pk["hbm_bytes_per_s"])
