"""The table of peaks and the operations and bytes a kernel needs, from
its shapes: the numerator of every roofline share.  Kept with the
benchmark so that a faster kernel raises its share and a recount of the
operations cannot.

The verify ladder (``ops/p256v3.py`` ``verify_batch`` over ``ops/rns.py``
as of PR 22).  One Montgomery multiplication (``rns._mont_mul_arr``) is
two base extensions (``rns._extend``); an extension's only matrix work
is one ``[rows, 2·N_CH] @ [2·N_CH, 3·N_CH]`` bf16 matmul (6-bit chunks of
the N_CH = 23 source residues against the three-block weight matrix), so

    matmul FLOP per Montgomery mul per lane = 2 · (2 · 46 · 69) = 12,696.

Montgomery muls per lane, by the point formulas' stacked dispatches
(``pt_add`` 6+2+6 = 14, ``pt_add_mixed`` 6+1+6 = 13, ``pt_double``
6+2+5 = 13):

    into Montgomery form (qx, qy, r, r+n)                         4
    on-curve check (y², x², x³, compare)                          4
    the u2·Q window table, 2^WINDOW − 2 = 14 pt_add             196
    STEPS = 64 steps of WINDOW = 4 doublings + pt_add
      + pt_add_mixed: 64 · (4·13 + 14 + 13)                    5056
    not-infinity, and x(R) against r and r+n (2 mul + 3 compare)  5
                                                          total 5265

so one lane needs 5265 · 12,696 = 66,844,440 matmul FLOP and a 3072-lane
launch 205.3 GFLOP.  The elementwise int32 work around the matmuls (the
mod-m reductions, about as many operations again) runs on the vector
unit and is not counted: the share below is of the MXU's peak.  Bytes:
the int16 launch frame in (4·46 residues + 2·64 window digits + 2 flags
= 314 columns per lane) and one verdict byte per lane out; the ladder's
state lives on the chip.
"""

from __future__ import annotations

#: ``device_kind`` as jax reports it → peaks of one chip.  A device that
#: is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}

N_CH = 23
WINDOW = 4
STEPS = 64
MUL_FLOP = 2 * (2 * (2 * N_CH) * (3 * N_CH))
PT_ADD, PT_ADD_MIXED, PT_DOUBLE = 14, 13, 13
FRAME_COLS = 4 * 2 * N_CH + 2 * STEPS + 2


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def verify_muls_per_lane() -> int:
    table = ((1 << WINDOW) - 2) * PT_ADD
    ladder = STEPS * (WINDOW * PT_DOUBLE + PT_ADD + PT_ADD_MIXED)
    return 4 + 4 + table + ladder + 5


def verify_work(lanes: int) -> dict:
    """Matmul FLOP and HBM bytes one verify launch of ``lanes`` needs."""
    return {"flop": lanes * verify_muls_per_lane() * MUL_FLOP,
            "bytes": lanes * (FRAME_COLS * 2 + 1)}


def least_seconds(work: dict, device_kind: str) -> tuple:
    """→ (the least time the chip could take, which bound applies)."""
    pk = peaks(device_kind)
    t_flop = work["flop"] / pk["bf16_flop_per_s"]
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    return max(t_flop, t_bytes), ("compute" if t_flop >= t_bytes
                                  else "memory")
