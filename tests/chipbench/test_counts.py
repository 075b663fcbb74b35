"""The yardstick's counts against hand counts, the table of peaks, and
the harness's refusal to run without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import flops
from chipbench.registry import Registry

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def reg():
    return Registry(REPO)


def nanogpt(reg):
    cfg = reg.cell("nanogpt-124m.top10-natural").config
    return reg.reference(cfg), cfg["arch"]


def test_nanogpt_model_flops_per_step(reg):
    fam, arch = nanogpt(reg)
    # 6 x (84.9M hidden + 38.6M tied head) x 8192 tokens plus attention,
    # 4 x 12 layers x 1024 x 768 per token forward, x3 for backward
    hidden = 12 * (4 * 768 * 768 + 2 * 768 * 3072)
    hand = 3 * 8192 * (2 * hidden + 2 * 768 * 50304
                       + 12 * 4 * 1024 * 768)
    got = fam.train_flops_per_token(arch, 1024) * 8192
    assert got == pytest.approx(hand, rel=1e-12)
    assert got == pytest.approx(7.0e12, rel=0.01)


@pytest.mark.parametrize("m,n", [(768, 768), (768, 3072), (512, 2048)])
def test_newton_schulz_flops(m, n):
    # upper triangles of the symmetric gram (m^2 n) and of its square
    # (m^3), and the product with X (2 m^2 n)
    want = 3 * m * m * n + m ** 3
    assert flops.ns_flops(m, n) == want
    assert flops.ns_flops(n, m) == want     # orientation does not matter
    # what a kernel forming the full gram computes is more
    assert 4 * m * m * n + 2 * m ** 3 > want


def test_nanogpt_newton_schulz_flops_per_step(reg):
    fam, arch = nanogpt(reg)
    per_layer = 4 * flops.ns_flops(768, 768) + 2 * flops.ns_flops(768, 3072)
    got = flops.ns_step_flops(fam.leaf_specs(arch), 5)
    assert got == 5 * 12 * per_layer
    # 1.63e12 with the full gram and its full square
    assert got == pytest.approx(1.14e12, rel=0.01)


def test_natural_encode_bytes_counts_kept_values(reg):
    fam, arch = nanogpt(reg)
    specs = fam.leaf_specs(arch)
    kept = (12 * (4 * 58983 + 2 * 235930)      # ceil(0.1 * slice)
            + 3863348 + 78644)                  # embed, pos_embed
    assert flops.natural_encode_bytes(specs, 0.1, 2) == 2 * kept * 4


def test_peaks_refuse_an_unknown_device(reg):
    assert reg.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        reg.peak("TPU v4")


@pytest.mark.parametrize("platform,count,need,ok", [
    ("tpu", 1, 1, True), ("tpu", 4, 1, True), ("tpu", 1, 4, False),
    ("cpu", 8, 1, False), ("gpu", 1, 1, False)])
def test_chip_check(platform, count, need, ok):
    from chipbench import run
    dev = type("Dev", (), {"platform": platform})()
    assert run.has_chips([dev] * count, need) is ok


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "nanogpt-124m.top10-natural", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(REPO, env)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A directory holding BENCHMARK.json and the benchmark's own files,
    and not the program, gives no result, also past the look for a
    chip."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = _run(tmp_path, env)
    assert p.returncode != 0 and p.stdout == ""
    code = ("import sys; sys.path.insert(0, '.'); "
            "from chipbench import run; "
            "run.has_chips = lambda devices, n: True; "
            "sys.exit(run.main(['--workload', "
            "'nanogpt-124m.top10-natural', '--seed', '1', '--seconds', "
            "'1']))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "repro" in p.stderr
