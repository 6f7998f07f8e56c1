"""The fp8 control, put in the program's place and read by the same
predicate, must come out as not correct under the limit the chip cells
are held to, where the program comes out correct, at a size a test run
can hold: on one device (the exec backend) and on four (the shard_map
backend)."""

import json

import pytest

from conftest import (BENCH, CPU_PEAKS, TINY_CELL, make_root,
                      run_four_chips)

import harness


@pytest.mark.parametrize("seed", [11, 2_200_000_012, 13])
def test_control_fails_where_the_program_passes(seed, tmp_path,
                                                compile_cache):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    limits = {json.loads((BENCH.parent / c["file"]).read_text())
              ["check"]["max_abs_err"] for c in spec["configs"]}
    root = make_root(tmp_path)
    for limit in sorted(limits):
        path = root / "bench" / "configs" / "tiny-mla.4inst-cpu.json"
        config = json.loads(path.read_text())
        config["check"]["max_abs_err"] = limit
        path.write_text(json.dumps(config))
        done = harness.run(root, TINY_CELL, seed, 0.5, False,
                           require_tpu=False, peaks=CPU_PEAKS,
                           control=True, log=lambda msg: None)
        chk = done.result["checks"]
        assert done.result["correct"] is True
        assert done.control_correct is False
        assert chk["control_over_limit"]["value"] > 0
        assert chk["control_max_abs_err"]["value"] > 2 * limit


def test_four_chip_control_fails_where_the_program_passes(tmp_path):
    """The four-chip cell's control through the four-device harness: the
    program reads correct, the control not, its gap above twice the limit
    of the chip configuration that runs the shard_map backend."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    limits = [json.loads((BENCH.parent / c["file"]).read_text())
              for c in spec["configs"]]
    limit = max(c["check"]["max_abs_err"] for c in limits
                if c["serving"]["backend"] == "shard_map")
    res = run_four_chips(tmp_path, control=True)
    assert res["device"]["count"] == 4
    assert res["correct"] is True
    assert res["control_correct"] is False
    chk = res["checks"]
    assert chk["control_over_limit"]["value"] > 0
    assert chk["control_max_abs_err"]["value"] > 2 * limit
