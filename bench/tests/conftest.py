"""Shared set-up of the benchmark's own tests (run them by path:
`python -m pytest bench/tests`). They run on the CPU at tiny sizes."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the CPU's entry in a peak table handed to the harness in place of
# bench/peaks.json, which lists chips only
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                     "source": "a round number for tests; not a device"}}
TINY_CELL = "tiny.fleet"
# the chip cell whose per-layer metrics the tiny cell reports, by chips
STANDS_FOR = {1: "dsv2-1chip.agent-fleet", 4: "dsv2-4chip.agent-fleet"}


def make_root(tmp: pathlib.Path, *, chips: int = 1, backend: str = "exec",
              extra_metric: str = "", model: str = "") -> pathlib.Path:
    """A checkout-shaped directory holding one tiny cell: the test data's
    configuration and traffic as new files, the benchmark's metric
    readers, and optionally one more reader and the hook module
    `data/<model>.py`, all named in its own BENCHMARK.json. The tiny cell
    reports the per-layer metrics of the chip cell it stands for
    (STANDS_FOR)."""
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    config = json.loads((DATA / "tiny-mla.4inst-cpu.json").read_text())
    config["serving"].update(chips=chips, backend=backend)
    if model:
        (bench / "models").mkdir()
        shutil.copy(DATA / f"{model}.py", bench / "models")
        config["model"] = f"models/{model}.py"
    (bench / "configs" / "tiny-mla.4inst-cpu.json").write_text(
        json.dumps(config))
    shutil.copy(DATA / "tiny-fleet.json", bench / "traffic")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-mla.4inst-cpu", "source": "test data",
                        "file": "bench/configs/tiny-mla.4inst-cpu.json",
                        "reduced": [], "why": "CPU tests"}]
    spec["workloads"] = [{"name": TINY_CELL, "config": "tiny-mla.4inst-cpu",
                          "traffic": "tiny-fleet", "chips": chips,
                          "why": "CPU tests"}]
    like = STANDS_FOR[chips]
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if like in m.pop("workloads", [like])]
    if extra_metric:
        (bench / "metrics" / f"{extra_metric}.py").write_text(
            "def read(window):\n"
            "    return float(sum(s.rows for s in window.steps))\n")
        spec["per_layer"].append({
            "name": extra_metric, "unit": "rows", "better": "higher",
            "source": "host_clock", "layer": "test", "moves": "rows_per_s"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """A private compile cache, so no test writes into the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


# faults planted in the shard_map backend before the four-device run
FOUR_CHIP_FAULTS = {
    "stale_state": """
        orig = S.ShardMapExecBackend.await_result
        prev = {}
        def await_result(self, engine, ticket, _o=orig):
            done = _o(self, engine, ticket)
            now = dict(done.outputs)
            done.outputs.update({k: v for k, v in prev.items()
                                 if k in done.outputs})
            prev.clear()
            prev.update(now)
            return done
        S.ShardMapExecBackend.await_result = await_result
    """,
    "half_of_each_chunk": """
        orig = S.absorbed_partial
        def half(cfg, q, ckv, mask=None, _o=orig):
            n = ckv.shape[0] // 2
            return _o(cfg, q, ckv[:n], None if mask is None else mask[:n])
        S.absorbed_partial = half
    """,
    "exchange_left_out": """
        for name in ("pairwise_ship", "fanout_gather"):
            orig = getattr(S, name)
            setattr(S, name, lambda *a, _o=orig, **k: _o(*a, **k) * 0)
    """,
    "answer_altered": """
        orig = S.merge_tree
        def merge_tree(parts, _o=orig):
            p = _o(parts)
            return p._replace(o=p.o.at[..., 0, 0].add(0.5))
        S.merge_tree = merge_tree
    """,
}

FOUR_CHIPS = """
import json, sys
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import harness
from conftest import CPU_PEAKS, TINY_CELL
from repro.serving.backends import shard_map as S
{fault}
done = harness.run({root!r}, TINY_CELL, 2_200_000_654, 0.5, {trace!r},
                   require_tpu=False, peaks=CPU_PEAKS, control={control!r},
                   log=lambda msg: None)
print(json.dumps(dict(done.result, control_correct=done.control_correct)))
"""


def run_four_chips(tmp: pathlib.Path, *, fault: str = "",
                   trace: bool = False, control: bool = False) -> dict:
    """The tiny cell on four host devices through the shard_map backend,
    in a subprocess (the device count is fixed when JAX starts), with the
    named FOUR_CHIP_FAULTS planted, and with `control` the control read
    beside the program. Returns the result line, with the control's
    verdict under `control_correct` (None without `control`)."""
    root = make_root(tmp / "root", chips=4, backend="shard_map")
    code = FOUR_CHIPS.format(
        src=str(ROOT / "src"), bench=str(BENCH), tests=str(BENCH / "tests"),
        fault=textwrap.dedent(FOUR_CHIP_FAULTS.get(fault, "")),
        trace=trace, control=control, root=str(root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jc"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
