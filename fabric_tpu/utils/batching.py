"""Batch-shape bucketing shared by the TPU data-plane kernels.

Kernels compile once per static shape; bucketing batch sizes bounds the
number of compilations on the block-commit path (block tx counts vary
per block — reference: orderer/common/blockcutter/blockcutter.go:74-130
cuts variable-size batches).

This module is the ONE place that says which programs a block needs:
:func:`block_shapes` maps a block's counts to the static shapes of its
verify program (``ops/p256v3.py``) and of its fused stage-2 program
(``peer/device_block.py``), and :func:`channel_shapes` enumerates those
shapes over every block a channel's orderer can cut.  Everything that
pads (``p256v3._bucket``, the validator's policy-group builders,
``ops/mvcc.py``'s static arrays) asks here, so the set a warm-up walks
(``BlockValidator.warmup``) is the set the commit path keys.
"""

from __future__ import annotations

from typing import NamedTuple

#: smallest verify batch, stage-2 tx axis and policy-group entry axis
MIN_LANES = 16
#: smallest endorsement-slot axis of a policy group
MIN_SLOTS = 4
#: verify batches are powers of two up to here, then multiples of it
VERIFY_STEP = 512


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(0, (n - 1)).bit_length()


class BlockShapes(NamedTuple):
    """The static shapes one block's two device programs are keyed on."""

    verify: int   # lanes of the verify batch (``n_sig`` of stage 2)
    txs: int      # T: stage 2's tx axis
    entries: int  # E: (tx, namespace) rows of one policy group
    slots: int    # S: endorsement slots per entry
    dims: tuple   # (R, W, Q): reads, writes, range queries per tx


def block_shapes(txs: int = 0, entries: int = 0, signatures: int = 0,
                 endorsements: int = 0, reads: int = 0, writes: int = 0,
                 ranges: int = 0) -> BlockShapes:
    """A block's counts → the shapes its programs pad to.

    ``signatures``: the verify batch (creators + endorsements): powers
    of two up to 512, then multiples of 512 — a 1000-tx block's 3000
    signatures pad to 3072, not 4096 (padding lanes are wasted MXU
    work), and few distinct shapes keep the compile cache small.
    ``txs``: the block's envelopes.  ``entries``: the live (tx,
    namespace) pairs judged under one policy (a block has one such
    count per policy in play).  ``endorsements`` / ``reads`` / ``writes``
    / ``ranges``: the most any one tx carries.  A count left at 0 gives
    its axis's smallest shape."""
    if signatures <= VERIFY_STEP:
        verify = max(MIN_LANES, next_pow2(signatures))
    else:
        verify = -(-signatures // VERIFY_STEP) * VERIFY_STEP
    return BlockShapes(
        verify=verify,
        txs=max(MIN_LANES, next_pow2(txs)),
        entries=max(MIN_LANES, next_pow2(entries)),
        slots=max(MIN_SLOTS, next_pow2(endorsements)),
        dims=(next_pow2(reads), next_pow2(writes), next_pow2(ranges)),
    )


def channel_shapes(max_tx: int, signatures_per_tx: int, reads: int,
                   writes: int, ranges: int) -> dict:
    """Every :class:`BlockShapes` a block of 1..``max_tx`` txs can have
    when each tx carries ``signatures_per_tx`` signatures (one creator's
    and its endorsements), touches one namespace and
    reads / writes / ranges at most that many keys → ``{shapes: the
    largest tx count that has them}``, largest first.  A block of that
    many txs, or a launch of those shapes, warms the programs of every
    smaller block that shares them."""
    out: dict = {}
    for n in range(int(max_tx), 0, -1):
        out.setdefault(block_shapes(
            txs=n, entries=n, signatures=n * signatures_per_tx,
            endorsements=signatures_per_tx - 1, reads=reads,
            writes=writes, ranges=ranges), n)
    return out
