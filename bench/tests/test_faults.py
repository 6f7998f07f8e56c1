"""Faults planted under the timed path, which the check must catch: the
tiny CPU cell runs as a chip run does, past the platform check, with one
of the program's functions broken, and `correct` has to come out false.

One chip: a step that hands back the previous step's state, half of each
chunk left out of the softmax, the query exchange left out, an answer
altered where it is produced. Four chips (a subprocess with four host
devices, the shard_map backend): the same faults planted there, the
chips' exchange left out among them."""

import pytest

from conftest import (CPU_PEAKS, FOUR_CHIP_FAULTS, TINY_CELL, make_root,
                      run_four_chips)

import harness


def run_tiny(root):
    return harness.run(root, TINY_CELL, 2_200_000_321, 0.5, False,
                       require_tpu=False, peaks=CPU_PEAKS,
                       log=lambda msg: None).result


def stale_state(monkeypatch):
    """Each step returns the previous step's outputs where it has them."""
    from repro.serving.backends import jax_exec
    orig = jax_exec.JaxExecBackend.execute
    prev = {}

    def execute(self, engine, plan):
        done = orig(self, engine, plan)
        now = dict(done.outputs)
        done.outputs.update({k: v for k, v in prev.items()
                             if k in done.outputs})
        prev.clear()
        prev.update(now)
        return done
    monkeypatch.setattr(jax_exec.JaxExecBackend, "execute", execute)


def half_of_each_chunk(monkeypatch):
    """Attention over the first half of a chunk's tokens only: the mean
    taken over the rest."""
    from repro.core import routing
    from repro.serving.backends import jax_exec
    for mod in (jax_exec, routing):
        orig = mod.absorbed_partial

        def half(cfg, q, ckv, mask=None, _orig=orig):
            n = ckv.shape[0] // 2
            return _orig(cfg, q, ckv[:n], None if mask is None else mask[:n])
        monkeypatch.setattr(mod, "absorbed_partial", half)


def exchange_left_out(monkeypatch):
    """Routed queries never reach the holder: its partial is the merge
    identity, as if nothing came back."""
    from repro.core.merge import Partial
    from repro.serving.backends import jax_exec

    def route_batched(cfg, qs, holder_arrays, masks=None):
        return [Partial.identity(q.shape[:-1], cfg.kv_lora_rank)
                for q in qs]
    monkeypatch.setattr(jax_exec, "route_batched", route_batched)


def answer_altered(monkeypatch):
    """One element of every merged output is changed where it is made."""
    from repro.serving.backends import jax_exec
    orig = jax_exec.merge_tree

    def merge_tree(parts):
        p = orig(parts)
        return p._replace(o=p.o.at[..., 0, 0].add(0.5))
    monkeypatch.setattr(jax_exec, "merge_tree", merge_tree)


def test_sound_program_is_correct(tmp_path, compile_cache):
    assert run_tiny(make_root(tmp_path))["correct"] is True


@pytest.mark.parametrize("fault", [stale_state, half_of_each_chunk,
                                   exchange_left_out, answer_altered])
def test_planted_fault_is_not_correct(fault, tmp_path, compile_cache,
                                      monkeypatch):
    fault(monkeypatch)
    res = run_tiny(make_root(tmp_path))
    assert res["correct"] is False
    assert res["failed"] > 0
    chk = res["checks"]["max_abs_err"]
    assert chk["value"] > chk["limit"]


@pytest.mark.parametrize("fault", ["", *FOUR_CHIP_FAULTS],
                         ids=lambda f: f or "sound")
def test_four_chips_exchange_left_out_is_not_correct(fault, tmp_path):
    res = run_four_chips(tmp_path, fault=fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is (not fault)
    if fault:
        chk = res["checks"]["max_abs_err"]
        assert res["failed"] > 0 and chk["value"] > chk["limit"]
