"""Classify on Kimi Linear: the ``classify`` driver's closed loop of
prompts through the backbone and the DWN head, with the Kimi Linear
backbone's weights (``bench/kimi_weights.py``) and plain reference
(``bench/reference_kimi.py``).

The mix keys, the prompts, the window and what ``serve_samples_per_s``
counts are ``bench/drivers/classify.py``'s.  The configuration file holds
the published config.json keys as cut (``reduced``), the program's arch
name (``arch``), the head's sizes (``head``) and ``feature_err_limit``.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import kimi_weights, reference, serving
from bench.drivers import classify
from bench.drivers.classify import _Phases, head_artifact, prompts
from bench.harness import Compared
from bench.reference_kimi import Reference, feature_error


def program_arch(cfg: dict):
    """The program's arch for the configuration: its registered published
    config, checked against the file's numbers, cut as ``reduced`` says."""
    from repro.configs import get_arch
    arch = get_arch(cfg["arch"])
    got = {"hidden_size": arch.d_model, "intermediate_size": arch.dense_ff,
           "moe_intermediate_size": arch.d_ff,
           "num_shared_experts": arch.shared_ff // arch.d_ff,
           "num_attention_heads": arch.num_heads,
           "num_key_value_heads": arch.num_kv_heads,
           "kv_lora_rank": arch.kv_lora_rank,
           "qk_nope_head_dim": arch.qk_nope_head_dim,
           "qk_rope_head_dim": arch.qk_rope_head_dim,
           "v_head_dim": arch.v_head_dim,
           "num_experts_per_token": arch.top_k,
           "routed_scaling_factor": arch.routed_scaling,
           "moe_router_activation_func": arch.router,
           "first_k_dense_replace": arch.first_dense_layers,
           "kda_gate_rank": arch.kda_gate_rank,
           "vocab_size": arch.vocab_size, "rms_norm_eps": arch.norm_eps,
           "tie_word_embeddings": arch.tie_embeddings,
           "mla_use_nope": not arch.rope_theta,
           "published": {
               "num_hidden_layers": arch.num_layers,
               "num_experts": arch.num_experts,
               "linear_attn_config": {
                   "full_attn_layers": [i + 1 for i, t in enumerate(
                       arch.layer_types) if t == "mla"],
                   "head_dim": arch.head_dim, "num_heads": arch.kda_heads,
                   "kda_layers": [i + 1 for i, t in enumerate(
                       arch.layer_types) if t == "kda"],
                   "short_conv_kernel_size": arch.ssm_conv}}}
    want = {k: cfg[k] for k in got}
    want["published"] = {k: cfg["published"][k]
                         for k in got["published"]}
    if got != want or cfg["num_expert_group"] != 1:
        raise ValueError(f"arch {cfg['arch']!r} is {got}, the "
                         f"configuration file says {want}")
    return dataclasses.replace(
        arch, num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(kimi_weights.layer_kinds(cfg)),
        experts_held=cfg["num_experts"])


class Session(classify.Session):
    """The classify session on the Kimi Linear backbone."""

    def __init__(self, cell, seed: int, seconds: float, span):
        from repro.serving import ServingEngine
        from repro.serving.continuous import SLOConfig
        self.cell, self.seed, self.span = cell, seed, span
        cfg, mix = cell.config, cell.mix
        clock = _Phases()
        arch = program_arch(cfg)
        art, self.head_weights = head_artifact(cfg, seed)
        params = kimi_weights.model(cfg, seed)
        jax.block_until_ready(params)
        clock("weights")
        # the start-up cross-check compiles and runs the shortest shape
        self.engine = ServingEngine(
            arch, params=params, dwn_head=art,
            min_bucket=mix["min_length"], max_bucket=mix["length_max"],
            step_tokens=mix["step_tokens"], verify=True, seed=seed)
        if not self.engine.head_bit_exact:
            raise RuntimeError("startup cross-check of the head failed")
        clock("engine")
        # every step shape, compiled and run once before timing
        self.engine.warmup()
        clock("warmup")
        self.prompts = prompts(mix, cfg["vocab_size"], seed)
        self.engine.start_serving(slo=SLOConfig(
            max_queue_samples=mix["in_flight"]))
        self.sample = serving.Reservoir(mix["check_requests"], seed)
        self._i = 0
        # one pass of the loop outside the window, as the classify
        # driver makes it
        for _, req in [self._submit() for _ in range(mix["in_flight"])]:
            req.future.result()
        clock("start")
        clock.report("set-up")

    def check(self):
        """Features of the sampled prompts against the float32 reference,
        the head's answers against the plain DWN on the served features,
        and the answers that never came."""
        cfg, seed = self.cell.config, self.seed
        picked = self.sample.items
        out = [Compared("answers_missing", self.missing, 0)]
        if not picked:
            return out + [Compared("answers_checked", 0, -1)]
        counts, pred, feats = (np.concatenate([v[j] for _, v in picked])
                               for j in range(3))
        clock = _Phases()
        ref = Reference(cfg).features(
            [self.prompts[k][0] for k, _ in picked],
            kimi_weights.embedding(cfg, seed),
            lambda i: kimi_weights.layer(cfg, seed, i),
            kimi_weights.final_norm(cfg), kimi_weights.lm_head(cfg, seed))
        clock("reference")
        clock.report("check")
        th, mapping, tables = self.head_weights
        ref_c, ref_p = reference.infer(feats, th, mapping, tables,
                                       cfg["head"]["classes"])
        off = (np.any(counts.astype(np.int64) != ref_c, axis=1)
               | (pred.astype(np.int64) != ref_p))
        return out + [
            Compared("feature_err", feature_error(feats, ref),
                     cfg["feature_err_limit"]),
            Compared("rows_off", int(off.sum()), 0)]


def setup(cell, seed: int, seconds: float, span) -> Session:
    return Session(cell, seed, seconds, span)
