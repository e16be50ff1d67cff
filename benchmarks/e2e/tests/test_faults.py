"""A whole run with the timed path broken underneath: `correct` must come
out false.  The harness's look for a chip is skipped (BENCH_E2E_REHEARSE:
the program's server on the CPU, the deployment cut to 1/20); everything
after it is run.py's own flow: boot, load, read-back, warm-up, the window,
the oracle, the result line.  The fault is planted where an answer is
produced, between the server's reply and the record the oracle reads.
Each case boots a server (about a minute); none of its numbers is a
device number.
"""

import json

import pytest

import dataset
import run

ARGS = ["--workload", "svc1k.pctl-6h", "--seed", "2700000033", "--seconds", "3", "--trace", "0"]


def value_altered(answer: dict) -> dict:
    """One group's p99 moved by two histogram buckets' worth."""
    g = min(answer)
    count, (p50, p99) = answer[g]
    return dict(answer, **{g: (count, [p50, p99 * 1.05 + 5.0])})


def group_dropped(answer: dict) -> dict:
    return {g: v for g, v in answer.items() if g != min(answer)}


def count_altered(answer: dict) -> dict:
    g = min(answer)
    return dict(answer, **{g: (answer[g][0] - 1, answer[g][1])})


# the fault planted -> the one number of `compared` that must read over its limit
FAULTS = {
    "sound": (None, None), "value": (value_altered, "value_gap_tol"),
    "group": (group_dropped, "groups_gap"), "count": (count_altered, "count_gap"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch, capfd):
    monkeypatch.setenv("BENCH_E2E_REHEARSE", "1")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("BYDB_WORKERS", "0")
    spoil, number = FAULTS[fault]
    real, seen = dataset.answer_of, [0]

    def answer_of(result):
        answer = real(result)
        seen[0] += 1
        # every tenth answer, the window's among them; set-up's read-back does not pass here
        return spoil(answer) if spoil and seen[0] % 10 == 0 else answer

    monkeypatch.setattr(dataset, "answer_of", answer_of)
    assert run.main(ARGS) == 0
    out, err = capfd.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared ")
    over = [k for k, (v, limit) in result["compared"].items() if v > limit]
    if spoil is None:
        assert result["correct"] is True and result["failed"] == 0 and over == []
    else:
        assert result["correct"] is False and over == [number]
        assert 0 < result["failed"] < result["attempted"]
