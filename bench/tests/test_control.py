"""The control (the reference at the next lower precision, in the
program's place) comes out not correct, and the program itself correct, on
three seeds at a small size of each configuration: retrieval on couplings
of one bit fewer; Max-Cut with its cut in bfloat16, at a size whose cuts
pass 256, where bfloat16 stops holding every integer."""

import pytest

from small import run_small

CASES = [("onn506.batch", None), ("maxcut_g1.batch", {"n": 200})]


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 987654321987])
@pytest.mark.parametrize("cell,config", CASES, ids=[c for c, _ in CASES])
def test_control_fails_program_passes(cell, config, seed):
    ctrl = run_small(cell, seed, control=True, config=config)
    assert not ctrl["correct"]
    assert ctrl["checks"]["mismatched_requests"]["value"] > 0
    prog = run_small(cell, seed, config=config)
    assert prog["correct"]
    assert prog["checks"]["mismatched_requests"]["value"] == 0
