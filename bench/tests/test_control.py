"""The control of the comparison, at a reduced size on the CPU: the
reference computed in bfloat16, put in the program's place, must read
above the limit on every seed while the program reads below it. On the
chip the same readings are made at the cell's own size by bench/control.py
(PERF.md gives them and the limits set from them)."""
import time

import jax
import pytest

from bench import check, harness
from bench.tests.test_window import LIMIT, cut


@pytest.mark.parametrize("config", ["zamba2-1.2b", "rwkv6-1.6b"])
@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_control_reads_above_the_limit(config, seed):
    cell, cfg = cut(config)
    got = harness.serve(cell, seed, 4.0, False, time.perf_counter(),
                        cfg=cfg, devs=jax.devices()[:1])
    r = check.readings(cell.spec, seed, got["window"], got["max_len"])
    assert r["tokens"] >= 10
    assert r["program"] <= LIMIT < r["control"], r
