"""DWNSpec: the single typed description of a DWN build.

Everything the paper shows can dominate DWN hardware cost — encoding
variant (TEN/PEN), thermometer resolution T, threshold placement, PEN
input width — plus the serving knobs (datapath backend, popcount
grouping) lives in one frozen, *validated-at-construction* dataclass.
A spec is the key of the whole lifecycle: ``DWNArtifact(spec)`` carries
it through train → freeze → pack → serve / hw-report, the sweep cache
fingerprints it, and checkpoints embed it so a reload reconstructs the
exact build.

Spec presets replace the old ``--arch dwn-jsc-*`` string glue: the
serving aliases are registered here (by ``repro.configs.dwn_jsc``) as
named specs, so CLIs resolve ``dwn-jsc-sm`` to a ``DWNSpec`` instead of
parsing arch-name suffixes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json

from ..configs.base import ArchConfig
from ..core.model import DWNConfig, JSC_PRESETS
from ..core.thermometer import PLACEMENTS

#: encoding variants: TEN receives pre-encoded thermometer bits, PEN
#: receives fixed-point features and encodes on chip (paper §II).
VARIANTS = ("TEN", "PEN")

#: popcount grouping modes (contig = paper Fig. 1; strided = the
#: shard-aligned optimization variant).
GROUPINGS = ("contig", "strided")

#: JSC tier -> LUT-layer width m (Table I model sizes).  Kept for
#: back-compat; per-workload tiers live on the workload registry entries
#: (``repro.workloads.get_workload(name).presets``).
TIERS = {name: cfg.lut_counts[-1] for name, cfg in JSC_PRESETS.items()}

_LUTS_TO_TIER = {m: name for name, m in TIERS.items()}


def _workload_presets(workload: str):
    """Tier name -> base DWNConfig for a workload (registry lookup)."""
    from ..workloads import get_workload
    return get_workload(workload).presets


def _serving_datapaths() -> list[str]:
    """Registered serving backend names (imported lazily so constructing
    a spec is what pulls in the serving registry, not importing this
    module)."""
    from ..serving.backends import available_backends
    return available_backends()


@dataclasses.dataclass(frozen=True)
class DWNSpec:
    """One validated DWN build point.

    Attributes:
      preset: JSC tier ("sm-10" | "sm-50" | "md-360" | "lg-2400") — fixes
        the LUT-layer width m.
      variant: "TEN" (bits arrive pre-encoded) or "PEN" (on-chip encoder).
      bits: thermometer bits per feature T (encoder resolution), >= 1.
      placement: threshold placement ("distributive" | "uniform" |
        "gaussian").
      input_bits: PEN fixed-point input width in *total* bits (1 sign +
        n fractional); must be set iff ``variant == "PEN"``.
      datapath: serving backend name ("fused-packed" | "packed-xla" |
        "float-oracle") — validated against the registry at
        construction.
      grouping: popcount grouping ("contig" | "strided").
      workload: registered workload name the spec trains/serves on
        ("jsc" | "mnist" | "lm-head" | ...); fixes the feature/class
        geometry and which preset tiers are valid.
      backbone: arch name of a feature-extractor backbone stage, for
        specs whose features come from a model (the DWN-head LM); None
        inherits the workload's backbone (also None for plain datasets).

    Raises ``ValueError`` at construction for any invalid combination;
    every message says what to change.
    """

    preset: str
    variant: str = "TEN"
    bits: int = 200
    placement: str = "distributive"
    input_bits: int | None = None
    datapath: str = "fused-packed"
    grouping: str = "contig"
    workload: str = "jsc"
    backbone: str | None = None

    def __post_init__(self):
        try:
            presets = _workload_presets(self.workload)
        except KeyError as e:
            raise ValueError(str(e.args[0])) from None
        if self.preset not in presets:
            raise ValueError(
                f"unknown DWN preset {self.preset!r} for workload "
                f"{self.workload!r}; known tiers: {sorted(presets)} "
                f"(each fixes the LUT-layer width m)")
        if self.variant not in VARIANTS:
            raise ValueError(
                f"unknown encoding variant {self.variant!r}; choose 'TEN' "
                f"(pre-encoded thermometer bits) or 'PEN' (on-chip encoder)")
        if not isinstance(self.bits, int) or self.bits < 1:
            raise ValueError(
                f"thermometer resolution bits={self.bits!r} is invalid: T "
                f"must be an integer >= 1 (thresholds per feature; the "
                f"paper uses T=200)")
        if self.placement not in PLACEMENTS:
            raise ValueError(
                f"unknown threshold placement {self.placement!r}; "
                f"supported placements: {list(PLACEMENTS)}")
        if self.variant == "PEN":
            if self.input_bits is None:
                raise ValueError(
                    "variant='PEN' requires input_bits (total fixed-point "
                    "input width, sign included — e.g. input_bits=9 for "
                    "the paper's (1, 8) grid)")
            if not isinstance(self.input_bits, int) or self.input_bits < 2:
                raise ValueError(
                    f"input_bits={self.input_bits!r} is invalid for PEN: "
                    f"need at least 2 (1 sign bit + >= 1 fractional bit)")
        elif self.input_bits is not None:
            raise ValueError(
                f"variant='TEN' must not set input_bits (got "
                f"{self.input_bits}): TEN models receive pre-encoded "
                f"thermometer bits, there is no on-chip comparator width. "
                f"Use variant='PEN' for on-chip encoding")
        if self.grouping not in GROUPINGS:
            raise ValueError(
                f"unknown popcount grouping {self.grouping!r}; supported: "
                f"{list(GROUPINGS)}")
        if self.datapath not in _serving_datapaths():
            raise ValueError(
                f"unregistered serving datapath {self.datapath!r}; "
                f"registered backends: {_serving_datapaths()} (register "
                f"new ones via repro.serving.backends.register_backend)")

    # -- derived views -------------------------------------------------

    @property
    def luts(self) -> int:
        """LUT-layer width m of the preset tier."""
        return _workload_presets(self.workload)[self.preset].lut_counts[-1]

    @property
    def frac_bits(self) -> int | None:
        """Fractional bits of the (1, n) fixed-point grid; None for TEN."""
        return None if self.input_bits is None else self.input_bits - 1

    @property
    def label(self) -> str:
        b = "" if self.input_bits is None else f"@{self.input_bits}b"
        wl = "" if self.workload == "jsc" else f"{self.workload}:"
        return (f"{wl}{self.preset}/{self.variant}{b}/T{self.bits}/"
                f"{self.placement}")

    @property
    def effective_backbone(self) -> str | None:
        """Backbone arch name: the explicit ``backbone`` field, else the
        workload's registered backbone, else None (plain dataset)."""
        if self.backbone is not None:
            return self.backbone
        from ..workloads import get_workload
        return get_workload(self.workload).backbone

    def dwn_config(self) -> DWNConfig:
        """The core model config (``repro.core.model.DWNConfig``) this
        spec trains and freezes — bit-identical to what the pre-spec glue
        constructed by hand."""
        return dataclasses.replace(_workload_presets(self.workload)[self.preset],
                                   bits_per_feature=self.bits,
                                   encoding=self.placement)

    def arch_config(self, name: str | None = None) -> ArchConfig:
        """A servable (unregistered) ArchConfig view of this spec, for
        code that still speaks ``ArchConfig`` (ServingEngine reports,
        dryrun shapes)."""
        cfg = self.dwn_config()
        return ArchConfig(
            name=name or f"dwn-{self.preset}-T{self.bits}-{self.placement}",
            family="dwn",
            num_layers=1, d_model=cfg.num_features,
            num_heads=0, num_kv_heads=0, d_ff=0,
            vocab_size=cfg.num_classes,
            dwn_luts=self.luts, dwn_bits=self.bits,
            dwn_encoding=self.placement, dwn_fused=True,
            dwn_datapath=self.datapath, dwn_grouping=self.grouping,
            source="repro.dwn.DWNSpec")

    # -- (de)serialization ---------------------------------------------

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # default workload/backbone are *omitted* so every pre-workload
        # fingerprint, sweep-cache key, and checkpoint stays valid
        if d["workload"] == "jsc":
            del d["workload"]
        if d["backbone"] is None:
            del d["backbone"]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "DWNSpec":
        return cls(**d)

    def fingerprint(self) -> str:
        """Stable 16-hex-char content hash of the spec — the cache /
        checkpoint identity."""
        blob = json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    # -- bridges from the legacy surfaces ------------------------------

    @classmethod
    def from_arch(cls, cfg: ArchConfig, *, variant: str = "TEN",
                  input_bits: int | None = None) -> "DWNSpec":
        """Derive the spec of a DWN ``ArchConfig`` (the supported bridge
        for legacy arch objects).

        ``dwn_datapath`` values that are not registered serving backends
        (the dryrun-only "corner"/"gather" variants) normalize to
        "fused-packed", exactly like the engine's pre-spec fallback.
        """
        if cfg.family != "dwn":
            raise ValueError(f"arch {cfg.name!r} is family={cfg.family!r}, "
                             f"not a DWN — no spec can be derived")
        preset = _LUTS_TO_TIER.get(cfg.dwn_luts)
        if preset is None:
            raise ValueError(
                f"arch {cfg.name!r} has dwn_luts={cfg.dwn_luts}, which is "
                f"not a JSC tier width ({sorted(_LUTS_TO_TIER)}); register "
                f"a preset tier first")
        datapath = cfg.dwn_datapath
        if datapath not in _serving_datapaths():
            datapath = "fused-packed"
        grouping = cfg.dwn_grouping if cfg.dwn_grouping in GROUPINGS \
            else "contig"
        return cls(preset=preset, variant=variant, bits=cfg.dwn_bits,
                   placement=cfg.dwn_encoding, input_bits=input_bits,
                   datapath=datapath, grouping=grouping)

    @classmethod
    def from_point(cls, point, *, datapath: str = "fused-packed",
                   grouping: str = "contig") -> "DWNSpec":
        """The spec of one ``repro.sweep.grid.SweepPoint`` (adds the
        serving knobs a grid point doesn't carry)."""
        return cls(preset=point.preset, variant=point.variant,
                   bits=point.bits, placement=point.placement,
                   input_bits=point.input_bits, datapath=datapath,
                   grouping=grouping,
                   workload=getattr(point, "workload", "jsc"))


# ---------------------------------------------------------------------------
# spec presets: named specs replacing the --arch dwn-jsc-* string glue
# ---------------------------------------------------------------------------

#: name -> DWNSpec (constructed) or dict of DWNSpec kwargs (deferred —
#: validation imports the serving registry, which config loading should
#: not pull in).
_PRESETS: dict[str, "DWNSpec | dict"] = {}


def register_preset(name: str, spec: DWNSpec | None = None,
                    **kwargs) -> None:
    """Register a named spec preset (``spec`` or deferred ``kwargs``).

    Deferred kwargs are validated (the spec is constructed) on first
    :func:`get_spec` access, so registering presets stays import-light.
    """
    assert (spec is None) != (not kwargs), "pass a spec OR kwargs"
    assert name not in _PRESETS, name
    _PRESETS[name] = spec if spec is not None else kwargs


def spec_presets() -> list[str]:
    """Names of every registered spec preset (loads the config registry
    so the ``dwn-jsc-*`` shims are visible)."""
    _ensure_presets()
    return sorted(_PRESETS)


def has_spec(name: str) -> bool:
    _ensure_presets()
    return name in _PRESETS


def get_spec(name: str) -> DWNSpec:
    """Resolve a registered spec preset by name."""
    _ensure_presets()
    if name not in _PRESETS:
        raise KeyError(f"unknown DWN spec preset {name!r}; registered: "
                       f"{sorted(_PRESETS)}")
    entry = _PRESETS[name]
    if isinstance(entry, dict):
        entry = DWNSpec(**entry)
        _PRESETS[name] = entry
    return entry


def _ensure_presets() -> None:
    # preset registration rides on the arch registry load (the thin shims
    # live in repro.configs.dwn_jsc)
    from ..configs import registry
    registry._load_all()


def resolve_spec(target) -> DWNSpec:
    """Normalize any legacy handle to a :class:`DWNSpec`.

    Accepts a DWNSpec (returned as-is), a registered preset / arch name,
    or a DWN ``ArchConfig``.
    """
    if isinstance(target, DWNSpec):
        return target
    if isinstance(target, str):
        if has_spec(target):
            return get_spec(target)
        from ..configs import get_arch
        target = get_arch(target)
    # ArchConfigs that shadow a registered spec preset (the non-JSC
    # families register both) resolve by name; only nameless/legacy
    # configs bridge through the JSC-tier from_arch path.
    if has_spec(getattr(target, "name", "")):
        return get_spec(target.name)
    return DWNSpec.from_arch(target)


__all__ = [
    "DWNSpec", "GROUPINGS", "TIERS", "VARIANTS", "get_spec", "has_spec",
    "register_preset", "resolve_spec", "spec_presets",
]
