"""CPU checks of the harness: it refuses to run without a TPU, touches no
device at import, and every name in BENCHMARK.json has its files."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    return env


def test_exits_nonzero_without_a_tpu():
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), timeout=240, cwd=ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_no_device_touched_at_import():
    code = ("import bench.run, bench.check, bench.trace, bench.traffic, bench.loop, "
            "bench.weights, bench.stats, bench.calibrate, bench.reference.qwen3, "
            "bench.metrics_common\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=_env(), timeout=240, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]


def test_every_name_has_its_files():
    from bench import run

    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
        hf = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        for key in configs[w["config"]]["reduced"]:
            assert key in hf["reduced_from"]
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and callable(run.load_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert NAME.match(m["name"])


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_readers_find_nothing_in_an_empty_run(name):
    from bench import run
    from bench.loop import Recorder
    from bench.tests import tiny

    hf = tiny.config(tiny.MOE)
    from bench import weights

    empty = run.Run(cell=SPEC["workloads"][0], hf=hf, dims=weights.dims_of(hf), mix={},
                    seconds=1.0, rec=Recorder(), trace=None,
                    peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    v = run.load_reader(name)(empty)
    assert v is None or v == 0.0


def test_program_config_skips_missing_fields_and_checks_published_keys(capsys):
    from bench import run
    from bench.tests import tiny

    hf = tiny.config(tiny.MOE, no_such_field=1)
    cfg = run.program_config(hf)
    assert cfg.route_mode == "sync" and not cfg.decode_plane
    assert cfg.capacity_factor * cfg.top_k == cfg.num_experts
    assert "no_such_field" in capsys.readouterr().err
    hf["hidden_size"] = 128
    with pytest.raises(SystemExit):
        run.program_config(hf)
