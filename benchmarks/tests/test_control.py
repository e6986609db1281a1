"""The control: the plain reference, put in the program's place and
computed in the nearest precision below the one the configuration states
(bfloat16 text scores; bfloat16 kNN products), must come out NOT correct
under the comparison that decides `correct`, and the full-precision
reference must come out correct. Three seeds, 20,000 docs (a size a test
run can hold; on the chip at 1,000,000 docs the same control is read with
`run.py --control 1`, PERF.md section 2).

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from compare import compare_all, reference_body  # noqa: E402
from selfcheck import small_cell  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2147483900, 3000000007])
@pytest.mark.parametrize("config_name",
                         ["msmarco-passage-bm25", "msmarco-knn768"])
def test_lower_precision_fails_and_full_precision_passes(config_name, seed):
    config, ref, bodies = small_cell(config_name, 20_000, seed, 64)
    g = config["guarantees"]
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    sound = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert sound["correct"], sound
    control = compare_all(
        g, bodies, ref.answer_many(bodies, precision="lower"), refs)
    assert not control["correct"], control
    value, _rel, limit = control["numbers"]["score_rel_max"]
    # the number the lower precision must fail, with room: PERF.md reads
    # >= 2.2e-4 (kNN, limit 1e-5) and >= 4e-3 (text, limit 1e-5)
    assert value > 10 * limit, control
