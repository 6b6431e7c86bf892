"""Roofline terms from a chip's published peaks.

Terms in seconds, from the peaks of the chip named by its ``device_kind``
(``PEAKS``):

    compute    = FLOPs          / (chips * peak FLOP/s)
    memory     = bytes          / (chips * HBM bytes/s)
    collective = collective_B   / (chips * ICI bytes/s per link)

``collective_bytes`` parses collective bytes from post-SPMD HLO text
(result-shape bytes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops). The post-SPMD module is the
per-device program, so parsed quantities are already per-chip.
"""

from __future__ import annotations

import re
from typing import Dict

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is TPU v5e (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interchip interconnect
# over four links = 50 GB/s per link.
V5E = "TPU v5 lite"
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; a device missing from
    ``PEAKS`` (a CPU among them) raises ``ValueError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# result type of a collective op, e.g.:  %x = bf16[8,128]{1,0} all-gather(
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
_TUPLE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind over the whole program."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        if line.lstrip().startswith("%") or " = " in line:
            lhs = line.split("=", 1)[0]
            rhs = line.split("=", 1)[1]
            # result may be a tuple (async pairs); sum every shape before
            # the op name
            head = rhs.split(kind)[0]
            total = sum(_shape_bytes(dt, dims)
                        for dt, dims in _TUPLE_RE.findall(head))
            out[kind] += total
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   coll_bytes: int, chips: int, *,
                   device_kind: str) -> Dict[str, float]:
    """Least seconds each resource needs on ``chips`` chips of
    ``device_kind``."""
    p = peaks(device_kind)
    return {
        "compute_s": flops / (chips * p["flops"]),
        "memory_s": bytes_accessed / (chips * p["hbm_bw"]),
        "collective_s": coll_bytes / (chips * p["ici_bw"]),
    }


def dominant(terms: Dict[str, float]) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k])
