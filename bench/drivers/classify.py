"""Classify: a closed loop of prompts through an LM backbone and the DWN
head on its pooled features, batched by the continuous scheduler into
padded (batch x length) steps.

Mix keys: ``in_flight`` (prompts in flight; each replaced the moment it
is answered), ``prompts`` (distinct prompts, sent in turn),
``length_median`` / ``length_sigma`` / ``length_min`` / ``length_max``
(prompt lengths: a lognormal, clipped), ``schedule_seed``, ``min_length``
and ``length_max`` (the ladder of step lengths), ``step_tokens`` (batch x
length of every step), ``check_requests`` (answered prompts compared
with the plain reference: a uniform sample drawn from the seed).

``serve_samples_per_s`` counts the prompts answered inside the window,
and those of the step its close cuts by the share of that step inside it.

Every seed gets the same prompt lengths in the same order, drawn once
from ``schedule_seed``; the token ids (and the weights) come from the
run's seed.  The first queued prompt sets each step's shape, so the
lengths' order, and whether a replacement is queued before the next step
forms, would each move the prompts a 10-s window answers by several
percent from run to run; replacements are sent from the answered
prompt's future callback, before the loop forms its next step.

The configuration file holds the published config.json keys as cut
(``reduced``), the program's arch name (``arch``), the head's sizes
(``head``) and ``feature_err_limit``, the largest ``feature_error`` of
the served features from the float32 reference that the check accepts.
The weights come from ``bench/granite_weights.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import threading
import time

import jax
import numpy as np

from bench import granite_weights, reference, serving
from bench.harness import Compared, Window
from bench.reference_granite import Reference, feature_error

def program_arch(cfg: dict):
    """The program's arch for the configuration: its registered published
    config, checked against the file's numbers, cut as ``reduced`` says."""
    from repro.configs import get_arch
    arch = get_arch(cfg["arch"])
    got = {"hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
           "shared_intermediate_size": arch.shared_ff,
           "num_attention_heads": arch.num_heads,
           "num_key_value_heads": arch.num_kv_heads,
           "num_experts_per_tok": arch.top_k, "vocab_size": arch.vocab_size,
           "mamba_n_heads": arch.ssm_expand * arch.d_model
           // arch.ssm_headdim,
           "mamba_d_head": arch.ssm_headdim, "mamba_d_state": arch.ssm_state,
           "mamba_n_groups": arch.ssm_ngroups, "mamba_d_conv": arch.ssm_conv,
           "mamba_expand": arch.ssm_expand,
           "mamba_chunk_size": arch.ssm_chunk,
           "rms_norm_eps": arch.norm_eps,
           "attention_multiplier": arch.attention_multiplier,
           "embedding_multiplier": arch.embedding_multiplier,
           "residual_multiplier": arch.residual_multiplier,
           "logits_scaling": arch.logits_scaling,
           "tie_word_embeddings": arch.tie_embeddings,
           "layer_types": list(arch.layer_types[:cfg["num_hidden_layers"]]),
           "published": {"num_hidden_layers": arch.num_layers,
                         "num_local_experts": arch.num_experts}}
    want = {k: cfg[k] for k in got}
    if got != want or cfg["position_embedding_type"] != "nope" \
            or arch.rope_theta:
        raise ValueError(f"arch {cfg['arch']!r} is {got}, the "
                         f"configuration file says {want}")
    return dataclasses.replace(
        arch, num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        experts_held=cfg["num_local_experts"])


def head_artifact(cfg: dict, seed: int):
    """The DWN head, its weights from the seed; returns (artifact,
    weights)."""
    from repro.core.model import FrozenDWN
    from repro.dwn import DWNArtifact, get_spec
    h = cfg["head"]
    spec = get_spec(h["spec_preset"])
    serving.check_spec(h, spec.dwn_config())
    th, mapping, tables = weights = granite_weights.head(cfg, seed)
    art = DWNArtifact(spec, frozen=FrozenDWN(spec.dwn_config(), th,
                                             [mapping], [tables], None))
    return art, weights


def prompt_lengths(mix: dict) -> np.ndarray:
    """The prompts' lengths, in the order they are sent."""
    rng = np.random.default_rng(mix["schedule_seed"])
    lengths = rng.lognormal(np.log(mix["length_median"]),
                            mix["length_sigma"], mix["prompts"])
    return np.clip(np.rint(lengths), mix["length_min"],
                   mix["length_max"]).astype(int)


def prompts(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The cell's prompts, each an int32 (1, length) token array."""
    rng = np.random.default_rng([seed, 2])
    return [rng.integers(0, vocab, (1, n), dtype=np.int32)
            for n in prompt_lengths(mix)]


class Session:
    def __init__(self, cell, seed: int, seconds: float, span):
        from repro.serving import ServingEngine
        from repro.serving.continuous import SLOConfig
        self.cell, self.seed, self.span = cell, seed, span
        cfg, mix = cell.config, cell.mix
        clock = _Phases()
        arch = program_arch(cfg)
        art, self.head_weights = head_artifact(cfg, seed)
        params = granite_weights.model(cfg, seed)
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
        # one pass of the loop outside the window: in a process that has
        # just compiled the steps, the first steps through the loop held
        # the device idle for seconds even after warmup() had run each
        # shape
        for _, req in [self._submit() for _ in range(mix["in_flight"])]:
            req.future.result()
        clock("start")
        clock.report("set-up")

    def _submit(self):
        k = self._i % len(self.prompts)
        self._i += 1
        with self.span("bench.submit"):
            return k, self.engine.submit_async(self.prompts[k])

    def _replace(self, future) -> None:
        """A prompt was answered: send the next one, before the step loop
        forms its next batch (the future's callbacks run in the loop's
        thread as it resolves the step), while the window is open."""
        with self._lock:
            if time.perf_counter() < self._t_end:
                try:
                    self._send_locked()
                except Exception as e:        # noqa: BLE001 - counted
                    self._send_errors.append(repr(e))

    def _send_locked(self) -> None:
        k, req = self._submit()
        self._sent.append((k, req))
        req.future.add_done_callback(self._replace)

    def window(self, seconds: float) -> Window:
        """``in_flight`` prompts, each replaced the moment it is answered:
        the replacements of a step's prompts are queued before the next
        step forms, so every run batches the same lengths alike."""
        mix, cont = self.cell.mix, self.engine._cont
        steps0, busy0 = cont.steps, cont.busy_s
        self._lock = threading.Lock()
        self._sent, self._send_errors = [], []
        self._t_end = t_end = time.perf_counter() + seconds
        # the scheduler's lock held: its first step sees every prompt
        with self._lock, cont._cond:
            for _ in range(mix["in_flight"]):
                self._send_locked()
        time.sleep(max(0.0, t_end - time.perf_counter()))
        with self._lock:               # no prompt is sent after the close
            sent = list(self._sent)
        lengths, late, failed = [], [], len(self._send_errors)
        self.missing = 0
        for k, req in sent:
            with self.span("bench.wait"):
                res = serving.wait(req, t_end)
            if res is None:
                self.missing += 1
            if res is None or res.shed is not None:
                failed += 1
                continue
            if req.t_done <= t_end:
                lengths.append(self.prompts[k].shape[1])
            else:
                late.append((req.t_done, req.t_start))
            self.sample.offer((k, res.value))
        return Window(
            metrics={"serve_samples_per_s":
                     (len(lengths) + _cut_share(late, t_end)) / seconds},
            counters={"steps": cont.steps - steps0,
                      "busy_s": cont.busy_s - busy0,
                      "served_samples": len(lengths),
                      "served_lengths": lengths, "window_s": seconds},
            attempted=len(sent) + len(self._send_errors), failed=failed)

    def finish(self) -> None:
        self.engine.stop_serving()
        del self.engine
        gc.collect()

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
            granite_weights.embedding(cfg, seed),
            lambda i: granite_weights.layer(cfg, seed, i),
            granite_weights.final_norm(cfg))
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


def _cut_share(late: list[tuple[float, float]], t_end: float) -> float:
    """The prompts of the step that the window's close cuts, counted by
    the share of that step's time inside the window.  ``late`` holds
    (done, start) of the prompts answered after the close; a step's
    prompts share both times.  A window holds about 23 steps of 2 to 16
    prompts, so counting the cut step whole or not at all would move a
    run's count by a step's prompts on jitter of a few milliseconds."""
    if not late:
        return 0.0
    done, start = min(late)
    prompts = sum(1 for d, _ in late if d == done)
    return prompts * min(1.0, max(0.0, (t_end - start) / (done - start)))


class _Phases:
    """Seconds between marks, printed to stderr as one line: where a run's
    set-up or check goes (a run's line holds only the whole)."""

    def __init__(self):
        self.t, self.seconds = time.perf_counter(), {}

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self.t, 3)
        self.t = now

    def report(self, what: str) -> None:
        print(f"bench: {what} seconds {json.dumps(self.seconds)}",
              file=sys.stderr, flush=True)


def setup(cell, seed: int, seconds: float, span) -> Session:
    return Session(cell, seed, seconds, span)
