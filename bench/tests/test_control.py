"""The control: the plain reference computed in float8, in the
program's place, over the same prompts and served tokens, comes out
not correct through the harness's own check; the program's own gaps
on the same sample stay inside the limits.

At the test fleet's size on the CPU (24 new tokens, every request of a
call read; six seeds): the program's widest gap is 0 on the probe and
0 to 0.00256 on the members; the float8 control's is 0.0026 to 0.0257
on the probe and 0.0182 to 0.0320 on the members. The test fleet's
limits are 0.006: above the program's 0.00256 by 2.3 times and below
the control's 0.0182 by 3. The member gap is the number the control
fails on every seed. The chip readings at the cells' sizes are in
PERF.md.
"""
import pytest


@pytest.mark.parametrize("seed", [2**33 + 11, 2**33 + 12, 2**33 + 13])
def test_control_fails_and_program_passes(tiny, seed):
    r = tiny(seed, control=True)
    checks = r["checks"]
    assert not r["correct"], checks
    assert checks["gap_member"]["value"] > checks["gap_member"]["limit"]
    # the sample was served and read whole: only the gaps fail
    for name in ("lanes_unmatched", "routes_wrong", "unanswered",
                 "records_unverified"):
        assert checks[name]["value"] == 0, checks
    assert r["checked"]["program_gap_probe"] <= checks["gap_probe"]["limit"]
    assert r["checked"]["program_gap_member"] <= \
        checks["gap_member"]["limit"]
