"""The harness's pieces on the CPU: traffic from the seed, the check of the
configuration file against the program, the refusal to run without a TPU,
and a cell added as new files only."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness
from chipbench.model import ConfigMismatch, program_config
from chipbench.tests.rehearse import overrides

ROOT = harness.root_of()
RUN_PY = os.path.join(ROOT, "chipbench", "run.py")
MIX = {"batch": 3, "seq": 15, "pool": 4, "zipf": 1.0}
VOCAB = 500


def _gen():
    return harness.load_module(os.path.join(ROOT, "chipbench", "generators",
                                            "lm_batches.py"))


def test_traffic_is_fixed_by_the_seed():
    a = np.asarray(_gen().make(MIX, 2 ** 40 + 3, VOCAB))
    b = np.asarray(_gen().make(MIX, 2 ** 40 + 3, VOCAB))
    assert a.shape == (4, 3, 16) and a.dtype == np.int32
    assert np.array_equal(a, b)


def test_every_seed_gets_the_same_work_in_another_order():
    a = np.asarray(_gen().make(MIX, 1, VOCAB))
    b = np.asarray(_gen().make(MIX, 2 ** 33 + 9, VOCAB))
    c = np.asarray(_gen().make(MIX, 2 ** 33 + 1 + 2 ** 32 * 4, VOCAB))
    assert a.shape == b.shape == c.shape
    assert not np.array_equal(a, b) and not np.array_equal(b, c)


def test_lengths_and_arrivals_follow_the_mix():
    tok = np.asarray(_gen().make(dict(MIX, pool=32, batch=16, seq=127), 7,
                                 VOCAB))
    assert tok.min() >= 0 and tok.max() < VOCAB
    rows = tok.reshape(-1, tok.shape[-1])
    assert len({r.tobytes() for r in rows}) == len(rows)   # every row differs
    freq = np.bincount(tok.ravel(), minlength=VOCAB) / tok.size
    # Zipf(1) over 500 ids: P(rank r) = 1 / (r * H_500), H_500 = 6.7928
    assert freq[0] == pytest.approx(1 / 6.7928, rel=0.05)
    assert freq[1] == pytest.approx(1 / (2 * 6.7928), rel=0.1)


def test_config_file_must_match_the_program():
    run = harness.open_run("train-wt103-262m", 1, 1.0, False)
    program_config(run)                           # as committed: agrees
    run.config["model"]["expert_size"] = 256
    with pytest.raises(ConfigMismatch):
        program_config(run)
    run = harness.open_run("train-wt103-262m", 1, 1.0, False)
    run.config["model"]["xl_memory"] = 256
    with pytest.raises(ConfigMismatch):
        program_config(run)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, RUN_PY, "--workload",
                        "train-wt103-262m", "--seed", "3", "--seconds",
                        "1", "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_a_cell_is_added_as_new_files(tmp_path):
    """A throwaway traffic mix, per-layer metric, limits file and cell:
    new files plus new entries in BENCHMARK.json, nothing else edited."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "testdata"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mix = harness.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                         "lm-b16-s512.json"))
    mix.update(batch=8, seq=1024)
    (bench_dir / "traffic" / "lm-b8-s1024.json").write_text(json.dumps(mix))
    (bench_dir / "limits" / "train-wt103-262m-long.json").write_text(
        (bench_dir / "limits" / "train-wt103-262m.json").read_text())
    (bench_dir / "metrics" / "steps.long.py").write_text(
        "def read(run, outcome):\n"
        "    return float(outcome.facts['steps'])\n")
    bench["workloads"].append({
        "name": "train-wt103-262m-long", "config": "wt103-262m-moe",
        "traffic": "lm-b8-s1024", "chips": 1, "why": "throwaway"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "train_tokens_per_s")["workloads"].append(
        "train-wt103-262m-long")
    bench["per_layer"].append({
        "name": "steps.long", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s",
        "workloads": ["train-wt103-262m-long"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = harness.open_run("train-wt103-262m-long", 5, 1.0, True,
                           bench_dir=str(bench_dir),
                           test=overrides(impl="ragged"))
    assert run.traffic["generator"] == "lm_batches"
    assert run.traffic["seq"] == 32          # the rehearsal's size rules
    res = harness.execute(run)
    assert res["metrics"]["steps.long"]["value"] > 0
    assert res["metrics"]["steps.long"]["unit"] == "steps"
