"""The measurement layer: compile a workload cell, harvest counters.

Mirrors the paper's two counter classes:
* performance counters — roofline-efficiency / useful-FLOP fraction (driven
  to LOW-value regions by the search);
* diagnostic counters — collective-traffic blowup, layout-thrash bytes, remat
  duplication, memory overshoot, sharding fallbacks (driven HIGH).

Split-phase measurement (ISSUE 5): ``measure_cell`` is now the composition
of two separable phases —

* :func:`lower_cell` — trace + jit-lower the cell (cheap, Python/GIL-bound)
  and derive a **structural fingerprint**: a hash of the canonicalized
  pre-XLA HLO text of the lowered module *plus* every non-compile input
  that feeds the counters (analytic floors, sharding-fallback count, mesh
  size).  Two points with equal fingerprints are guaranteed to produce
  byte-identical counter dicts, so the engine compiles only one of them.
* :func:`compile_lowered` — the expensive phase: XLA compile + memory /
  cost / HLO analysis, assembled into a :class:`Measurement`.

:func:`lowered_counters` is the fidelity-1 "lowered" tier: it runs the
single-pass HLO analyzer on the *pre-optimization* module text, giving real
structural counters (compiled FLOPs incl. remat recompute, layout-thrash
bytes) without compiling.  Pre-SPMD-partitioning modules carry no
collectives, trip counts, or remat metadata, so collective/memory counters
stay at their fidelity-0 surrogate estimates in that tier (see engine.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import re
import time
from typing import Any

from .. import hw
from ..launch import hloanalysis
from . import analytic


@dataclasses.dataclass
class Measurement:
    cell: Any
    compile_s: float
    memory: dict
    cost_analysis: dict
    hlo: dict
    roofline: dict
    floors: dict
    perf: dict          # performance counters (lower = worse)
    diag: dict          # diagnostic counters (higher = more stressed)
    tpu_custom_calls: int = 0   # Pallas kernel call sites Mosaic compiled

    def summary(self) -> dict:
        return {
            "arch": self.cell.cfg.name, "shape": self.cell.shape.name,
            "mesh": dict(self.cell.mesh.shape), "compile_s": self.compile_s,
            "memory": self.memory, "roofline": self.roofline,
            "floors": {k: v for k, v in self.floors.items()},
            "perf": self.perf, "diag": self.diag,
            "hlo": {k: v for k, v in self.hlo.items() if k != "op_hist"},
            "policy": dataclasses.asdict(self.cell.policy),
        }


# ------------------------------------------------------------ lower phase

# attributes of the HLO text that may vary without changing the program
# (defensive: jax 0.4.x emits no metadata in lowered text, but source-path
# metadata would break cross-machine fingerprint stability if it appeared)
_METADATA_RE = re.compile(r", metadata=\{[^{}]*\}")


def canonicalize_hlo_text(text: str) -> str:
    """Strip presentation-only noise so the fingerprint keys the *program*."""
    if "metadata=" in text:
        text = _METADATA_RE.sub("", text)
    return text


@dataclasses.dataclass
class LoweredCell:
    """Phase-1 artifact: a lowered (pre-XLA-optimization) cell.

    ``fingerprint`` hashes the canonical module text together with every
    counter input that is decided *before* compilation (analytic floors,
    useful-FLOP numerator, sharding fallbacks, mesh size): equal
    fingerprints ⇒ equal Measurement counters, by construction.
    """
    cell: Any
    lowered: Any            # jax.stages.Lowered
    text: str               # canonicalized pre-XLA HLO text
    lower_s: float
    floors: dict
    mf_useful: float
    fingerprint: str


def _floors_of(cell, chip: hw.ChipSpec):
    floors = analytic.step_floor_seconds(cell.cfg, cell.shape, cell.policy,
                                         cell.mesh, chip)
    mf_useful = (floors["matmul_model_flops"]
                 + analytic.attention_flops(cell.cfg, cell.shape)
                 + analytic.recurrence_flops(cell.cfg, cell.shape))
    return floors, mf_useful


def lower_cell(cell, chip: hw.ChipSpec) -> LoweredCell:
    """Trace + lower the cell (no XLA) and fingerprint its structure."""
    t0 = time.time()
    lowered = cell.lower()
    text = canonicalize_hlo_text(lowered.as_text(dialect="hlo"))
    lower_s = time.time() - t0
    floors, mf_useful = _floors_of(cell, chip)
    h = hashlib.sha256(text.encode())
    h.update(json.dumps(
        {"floors": {k: float(v) for k, v in sorted(floors.items())},
         "mf_useful": float(mf_useful),
         "fallbacks": int(cell.stats.fallbacks),
         "mesh_size": int(cell.mesh.size),
         "chip": chip.name},
        sort_keys=True).encode())
    return LoweredCell(cell, lowered, text, lower_s, floors, mf_useful,
                       h.hexdigest()[:24])


def lowered_counters(lc: LoweredCell, chip: hw.ChipSpec) -> dict:
    """Fidelity-1 structural counters from the pre-XLA module (no compile).

    The lowered module is un-partitioned (it computes the *global* program;
    SPMD collectives appear only during compilation), so structure-derived
    quantities are global and scaled per-device by the mesh size.  Returns a
    flat dict of the counters that are real at this tier; collective counts
    and peak memory are absent (the engine overlays surrogate estimates).
    """
    hlo = hloanalysis.analyze(lc.text)
    n = max(lc.cell.mesh.size, 1)
    floors = lc.floors
    flops_dev = hlo["flops"] / n
    bytes_dev = hlo["bytes_hbm"] / n
    compute_s = flops_dev / chip.peak_flops_bf16
    memory_s = bytes_dev / chip.hbm_bw
    # collective term is unknown pre-partitioning: bound by its floor
    bound_s = max(compute_s, memory_s, floors["collective_s"])
    return {
        "perf.roofline_efficiency":
            min(floors["floor_s"] / max(bound_s, 1e-30), 1.0),
        "perf.useful_flops_ratio":
            lc.mf_useful / max(hlo["flops"], 1.0),
        "diag.transpose_bytes": hlo["transpose_bytes"] / n,
    }


# ---------------------------------------------------------- compile phase

def compile_lowered(lc: LoweredCell, chip: hw.ChipSpec) -> Measurement:
    cell = lc.cell
    t0 = time.time()
    compiled = lc.lowered.compile()
    compile_s = lc.lower_s + (time.time() - t0)
    release = getattr(cell, "release_lowered", None)
    if release is not None:         # don't pin the traced module on the
        release()                   # Measurement's cell (see steps.py)

    ma = compiled.memory_analysis()
    memory = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                       + ma.output_size_in_bytes - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}   # None where the backend has none
    ca = {k: ca[k] for k in ("flops", "bytes accessed") if k in ca}
    text = compiled.as_text()
    hlo = hloanalysis.analyze(text)

    n = cell.mesh.size
    # per-device quantities straight from the partitioned module
    flops_dev = hlo["flops"]
    bytes_dev = hlo["bytes_hbm"]
    wire_dev = hlo["collective_wire_total"]
    compute_s = flops_dev / chip.peak_flops_bf16
    memory_s = bytes_dev / chip.hbm_bw
    coll_s = wire_dev / chip.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    bound_s = terms[dom]

    floors = lc.floors
    mf = floors["assignment_model_flops"]
    # scale-stable numerator: matmul params + attention + recurrence terms
    mf_useful = lc.mf_useful
    total_hlo_flops = flops_dev * n
    roofline = {
        **terms, "dominant": dom, "bound_s": bound_s,
        "hlo_flops_per_dev": flops_dev, "hlo_bytes_per_dev": bytes_dev,
        "collective_wire_per_dev": wire_dev,
        "collective_bytes_per_dev": hlo["collective_bytes_total"],
        "model_flops": mf,
        "model_flops_ratio": mf / max(total_hlo_flops, 1.0),
        "useful_flops_ratio": mf_useful / max(total_hlo_flops, 1.0),
        "roofline_fraction": floors["compute_s"] / max(bound_s, 1e-30),
    }

    perf = {
        # fraction of ideal step time actually achievable (<=1; low = anomaly)
        "roofline_efficiency": min(floors["floor_s"] / max(bound_s, 1e-30), 1.0),
        "useful_flops_ratio": roofline["useful_flops_ratio"],
    }
    peak = memory["peak_bytes"]
    diag = {
        "collective_blowup": wire_dev / max(floors["collective_floor"], 16e6),
        "collective_wire_bytes": wire_dev,
        "transpose_bytes": hlo["transpose_bytes"],
        "remat_flops_frac": hlo["remat_flops"] / max(flops_dev, 1.0),
        "memory_overshoot": peak / max(floors["memory_floor"], 1.0),
        "peak_bytes": peak,
        "hbm_oversubscribed": peak / chip.hbm_bytes,
        "shard_fallbacks": cell.stats.fallbacks,
        "n_allgather": hlo["collective_count"].get("all-gather", 0),
        "n_allreduce": hlo["collective_count"].get("all-reduce", 0),
        "n_alltoall": hlo["collective_count"].get("all-to-all", 0),
        "n_permute": hlo["collective_count"].get("collective-permute", 0),
    }
    return Measurement(cell, compile_s, memory, ca, hlo, roofline, floors,
                       perf, diag,
                       text.count('custom_call_target="tpu_custom_call"'))


def measure_cell(cell, chip: hw.ChipSpec) -> Measurement:
    """One-shot lower + compile + analyze (the pre-split entry point)."""
    return compile_lowered(lower_cell(cell, chip), chip)
