"""chip_smoke.py's own logic — argument handling, dataset, NumPy oracle,
answer checks — without a server, a chip, or JAX.  The run itself is
proved on the chip (`python chip_smoke.py` through the chip tool)."""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_import_and_oracle_need_no_jax():
    code = (
        "import sys, chip_smoke\n"
        "a = chip_smoke.parse_args(['--rows', '5000'])\n"
        "chip_smoke.oracle(chip_smoke.make_dataset(a.seed, a.rows))\n"
        "assert 'jax' not in sys.modules, 'chip_smoke imported jax'\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]


def test_args_default_to_the_full_size_and_reject_nonsense():
    args = chip_smoke.parse_args([])
    assert (args.rows, args.seed) == (chip_smoke.FULL_ROWS, 0)
    assert chip_smoke.FULL_ROWS == 10_000_000
    assert (chip_smoke.N_SERIES, chip_smoke.SHARDS) == (100_000, 4)
    assert chip_smoke.parse_args(["--seed", "7", "--rows", "9"]).seed == 7
    with pytest.raises(SystemExit):
        chip_smoke.parse_args(["--rows", "0"])
    with pytest.raises(SystemExit):
        chip_smoke.parse_args(["--platform", "cpu"])  # no such switch


def test_dataset_is_a_function_of_the_seed():
    a = chip_smoke.make_dataset(3, 4000)
    b = chip_smoke.make_dataset(3, 4000)
    c = chip_smoke.make_dataset(4, 4000)
    assert all(np.array_equal(a[k], b[k]) for k in ("svc", "hits", "value"))
    assert not np.array_equal(a["svc"], c["svc"])
    assert np.unique(a["ts"]).size == 4000  # no (series, ts) duplicates
    assert a["ts"][-1] - a["ts"][0] < chip_smoke.SPAN_MS


def test_oracle_matches_a_row_loop():
    data = chip_smoke.make_dataset(1, 3000)
    # few series so groups repeat and top-10 boundaries tie
    data["svc"] = data["svc"] % 37
    want = chip_smoke.oracle(data)

    by_region: dict = {}
    by_svc_ne3: dict = {}
    by_svc: dict = {}
    vals: dict = {}
    for s, r, h, v in zip(
        data["svc"].tolist(), data["region"].tolist(),
        data["hits"].tolist(), data["value"].tolist(),
    ):
        c = by_region.setdefault(r, [0, 0])
        c[0] += 1
        c[1] += h
        if r != 3:
            c = by_svc_ne3.setdefault(s, [0, 0])
            c[0] += 1
            c[1] += h
        c = by_svc.setdefault(s, [0, 0.0])
        c[0] += 1
        c[1] += v
        vals.setdefault(r, []).append(v)

    assert want["sum_by_region"] == {
        chip_smoke.region_name(r): {"count": c, "value": float(h)}
        for r, (c, h) in by_region.items()
    }
    top = sorted(by_svc_ne3, key=lambda s: (-by_svc_ne3[s][1], s))[:10]
    assert want["topn_sum_by_svc"] == {
        chip_smoke.svc_name(s): {
            "count": by_svc_ne3[s][0], "value": float(by_svc_ne3[s][1]),
        }
        for s in top
    }
    for r, vs in vals.items():
        vs.sort()
        got = want["percentile_by_region"][chip_smoke.region_name(r)]
        assert got["count"] == len(vs)
        assert got["value"] == [
            vs[math.ceil(q * len(vs)) - 1] for q in (0.5, 0.99)
        ]
    top = sorted(by_svc, key=lambda s: (-by_svc[s][1] / by_svc[s][0], s))[:10]
    assert set(want["topn_mean_by_svc"]) == {chip_smoke.svc_name(s) for s in top}
    for s in top:
        assert want["topn_mean_by_svc"][chip_smoke.svc_name(s)][
            "value"
        ] == pytest.approx(by_svc[s][1] / by_svc[s][0], rel=1e-12)


def test_check_answer_holds_counts_exact_and_values_to_contract():
    want = {"r0": {"count": 10, "value": 1000.0}}
    ok = {"r0": {"count": 10, "value": 1000.0 * (1 + 5e-6)}}
    chip_smoke.check_answer("sum_by_region", ok, want, 0.0)
    for bad in (
        {"r0": {"count": 9, "value": 1000.0}},  # a lost row
        {"r0": {"count": 10, "value": 1000.0 * (1 + 5e-5)}},  # past rtol
        {"r1": {"count": 10, "value": 1000.0}},  # wrong group
    ):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_answer("sum_by_region", bad, want, 0.0)
    # percentiles: one histogram bucket width
    want = {"r0": {"count": 10, "value": [50.0, 400.0]}}
    chip_smoke.check_answer(
        "percentile_by_region",
        {"r0": {"count": 10, "value": [51.5, 398.5]}}, want, 2.0,
    )
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_answer(
            "percentile_by_region",
            {"r0": {"count": 10, "value": [53.0, 400.0]}}, want, 2.0,
        )


def test_answer_of_reads_the_server_result_shape():
    result = {
        "groups": [["r1"], ["r0"]],
        "values": {"sum(hits)": [5.0, 7.0], "count": [2.0, 3.0]},
        "data_points": [],
    }
    assert chip_smoke.answer_of(result) == {
        "r1": {"count": 2, "value": 5.0},
        "r0": {"count": 3, "value": 7.0},
    }


def test_last_stdout_line_is_exactly_ok_and_device(monkeypatch, capsys):
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": "1"}
    monkeypatch.setattr(chip_smoke, "run_smoke", lambda args: device)
    assert chip_smoke.main(["--rows", "5000"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert type(last["device"]["count"]) is int
    assert lines[-2].startswith("summary: ")
    assert lines[-2].endswith('"claim": null}')


def test_a_failed_phase_prints_no_result(monkeypatch, capsys):
    def boom(args):
        raise chip_smoke.SmokeFailure("server reports cpu")

    monkeypatch.setattr(chip_smoke, "run_smoke", boom)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "{" not in out.out
    assert "server reports cpu" in out.err


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
