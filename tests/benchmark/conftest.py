"""The benchmark's tests drive the real commit path at a tiny size, and
the stage-2 programs they compile stay in ``device_block``'s
process-wide cache.  A later test of the same worker process that
asserts a program was compiled for its own run (``tests/
test_chip_smoke.py``) would then depend on which files ran before it:
each module here hands the cache back as it found it."""

import pytest


@pytest.fixture(scope="module", autouse=True)
def _stage2_programs_as_found():
    from fabric_tpu.peer import device_block

    before = dict(device_block._PROGRAM_CACHE)
    yield
    device_block._PROGRAM_CACHE.clear()
    device_block._PROGRAM_CACHE.update(before)
