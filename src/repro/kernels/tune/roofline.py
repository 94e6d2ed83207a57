"""Roofline models for autotune candidate pruning.

Per (family, shape, candidate config) this module estimates FLOPs, HBM
bytes, VMEM footprint, and grid-step count, and turns them into a modeled
time ``max(flops/peak, bytes/bw) + overhead * grid_steps``.  The sweep
harness measures only candidates whose modeled time is within a slack
factor of the best modeled time and whose tiles fit VMEM — the same
light-speed reasoning ``benchmarks/roofline.py`` applies to whole
compiled programs, applied per kernel tile here (that module reuses
``light_speed_s``/``roofline_fraction_us`` for its ``--tune-cache``
report).

Chip constants mirror the TPU v5e numbers in ``repro.launch.dryrun``
(which cannot be imported here: it must set ``XLA_FLAGS`` before jax
initializes, so importing it anywhere else would poison the device
count).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

from repro.kernels.sdca.kernel import vmem_bytes as sdca_vmem_bytes

# TPU v5e roofline constants — keep in sync with repro/launch/dryrun.py
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # bytes/s per chip
VMEM_BUDGET = 12 * 1024 * 1024  # VMEM a pruned-in candidate's tiles may take
GRID_STEP_OVERHEAD_S = 1e-6  # per-program dispatch floor
PRUNE_SLACK = 3.0


def light_speed_s(
    flops: float, bytes_moved: float, peak_flops: float = PEAK_FLOPS, hbm_bw: float = HBM_BW
) -> float:
    """Roofline lower bound for one kernel invocation."""
    return max(flops / peak_flops, bytes_moved / hbm_bw)


def roofline_fraction_us(measured_us: float, flops: float, bytes_moved: float) -> float:
    """measured / light-speed (>= 1; how far from the roofline we run)."""
    floor = light_speed_s(flops, bytes_moved) * 1e6
    return measured_us / floor if floor > 0 else 0.0


@dataclasses.dataclass
class CandidateEstimate:
    config: Dict[str, int]
    flops: float
    bytes_moved: float
    vmem_bytes: int
    grid_steps: int

    @property
    def t_model_s(self) -> float:
        return light_speed_s(self.flops, self.bytes_moved) + GRID_STEP_OVERHEAD_S * self.grid_steps


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def estimate(family: str, shape: Dict[str, int], config: Dict[str, int]) -> CandidateEstimate:
    """FLOPs/bytes/VMEM/grid model for one candidate (itemsize 4: tiles are
    staged in fp32)."""
    it = 4
    if family == "flash_attention":
        b, h, s, d = shape["b"], shape["h"], shape["s"], shape["d"]
        bq, bk = config["block_q"], config["block_k"]
        flops = 4.0 * b * h * s * s * d
        bytes_moved = 4.0 * b * h * s * d * it
        vmem = (bq * d + 2 * bk * d + 2 * bq * bk + bq * d) * it
        steps = b * h * _ceil_div(s, bq) * _ceil_div(s, bk)
    elif family == "flash_decode":
        b, h, s, d = shape["b"], shape["h"], shape["s"], shape["d"]
        bk = config["block_k"]
        flops = 4.0 * b * h * s * d
        bytes_moved = 2.0 * b * h * s * d * it
        vmem = (2 * bk * d + 2 * d + bk) * it
        steps = b * h * _ceil_div(s, bk)
    elif family == "flash_decode_paged":
        b, hk, g = shape["b"], shape["hk"], shape["g"]
        d, page, npp = shape["d"], shape["page"], shape["npp"]
        ppp = config["pages_per_program"]
        s = npp * page
        flops = 4.0 * b * hk * g * s * d
        bytes_moved = 2.0 * b * hk * s * d * it
        # one program per (row, page group), over every KV head: ppp K and V
        # pages of all heads, the scores, and the hk-wide acc/m/l scratch
        vmem = (2 * ppp * hk * page * d + hk * g * ppp * page + hk * g * (d + 2)) * it
        steps = b * _ceil_div(npp, ppp)
    elif family == "prefill_chunk":
        p, hk, g = shape["p"], shape["hk"], shape["g"]
        d, page, npp = shape["d"], shape["page"], shape["npp"]
        c = config["chunk"]
        s = npp * page
        n_chunks = _ceil_div(p, c)
        # every chunk re-gathers the full page row (the chunked-prefill
        # bytes tax) and attends c queries against s keys
        flops = 4.0 * hk * g * p * s * d
        bytes_moved = (2.0 * n_chunks * hk * s * d + 2.0 * hk * g * p * d) * it
        vmem = (c * g * d + 2 * 16 * d + 2 * c * 16) * it
        steps = n_chunks * hk * _ceil_div(c, 16) * _ceil_div(s, 16)
    elif family == "ssm_scan":
        bt, s, dn, n = shape["bt"], shape["s"], shape["dn"], shape["n"]
        chunk = config["chunk"]
        flops = 8.0 * bt * s * dn * n
        bytes_moved = 3.0 * bt * s * (dn + 2 * n) * it
        vmem = chunk * dn * (n + 2) * it
        steps = _ceil_div(s, chunk)  # sequential depth
    elif family == "sdca":
        m, nl, d = shape["m"], shape["nl"], shape["d"]
        h = shape.get("h", nl)
        flops = 4.0 * m * h * d
        bytes_moved = m * (nl * d + 2 * nl + 2 * d) * it
        # the pallas variant keeps the whole shard tile resident
        vmem = sdca_vmem_bytes(nl, d) if config.get("use_pallas") else 0
        steps = m
    else:
        raise ValueError(f"unknown kernel family {family!r}")
    return CandidateEstimate(
        config=config,
        flops=flops,
        bytes_moved=bytes_moved,
        vmem_bytes=int(vmem),
        grid_steps=int(steps),
    )


def prune(
    family: str,
    shape: Dict[str, int],
    candidates: Sequence[Dict[str, int]],
    slack: float = PRUNE_SLACK,
    vmem_budget: int = VMEM_BUDGET,
) -> Tuple[List[CandidateEstimate], int]:
    """Drop candidates that cannot fit VMEM or whose modeled time exceeds
    ``slack`` x the best modeled time.  Returns (survivors, n_pruned);
    always keeps at least one candidate (the best-modeled one)."""
    ests = [estimate(family, shape, c) for c in candidates]
    fits = [e for e in ests if e.vmem_bytes <= vmem_budget]
    if not fits:
        fits = [min(ests, key=lambda e: e.vmem_bytes)]
    t_best = min(e.t_model_s for e in fits)
    kept = [e for e in fits if e.t_model_s <= slack * t_best]
    return kept, len(ests) - len(kept)
