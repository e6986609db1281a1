"""Drives a whole run (the harness's look for a chip skipped:
`rehearse=True`: the CPU, the configuration's `rehearse_docs`) twice, each in a process of its
own as every run of the benchmark is: once sound, `correct` true; once
with the timed path broken underneath - the node's search answers
altered where they are produced - and `correct` must come out false.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = r"""
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
if {broken}:
    from elasticsearch_tpu.cluster.service import ClusterService
    real = ClusterService.search

    def swapped_hit(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        hits = resp.get("hits", {{}}).get("hits", [])
        if len(hits) >= 2:  # the best hit goes missing, the page shifts up
            resp = {{**resp, "hits": {{**resp["hits"],
                                       "hits": hits[1:] + hits[:1]}}}}
            resp["hits"]["hits"][-1] = {{**hits[0], "_id": "0"}}
        return resp

    ClusterService.search = swapped_hit
result = run.run_cell("msmarco-passage-bm25.load4", seed=11, seconds=2.0,
                      trace=False, rehearse=True)
print("RESULT " + json.dumps(result))
"""


def drive(broken: bool) -> dict:
    code = DRIVER.format(bench=BENCH, root=os.path.dirname(BENCH),
                         broken=broken)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       stdout=subprocess.PIPE, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("broken", [False, True])
def test_correct_is_true_when_sound_and_false_when_answers_are_altered(broken):
    result = drive(broken)
    assert result["attempted"] > 0 and result["failed"] == 0, result
    assert result["checks"]["answers_checked"][0] >= 32, result
    assert result["correct"] is (not broken), result
    if broken:
        assert result["checks"]["page_mismatches"][0] > 0, result
