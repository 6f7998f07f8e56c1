"""Test hooks: an engine with a selector and per-token index keys drawn
on the device from the seed, each build recorded as one JSON line in
`<this file>.calls`."""

import json
import pathlib

import harness

_KEY_WIDTH = 8


def build_engine(config, chunks, backend, seed):
    import jax
    from repro.serving.selection.service import IndexerService
    eng = harness.build_engine(config, chunks, backend, seed)
    eng.selector = IndexerService(mla=backend.cfg, dtype=backend.dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    keys = jax.random.normal(key, (len(chunks), chunks[0].shape[0],
                                   _KEY_WIDTH))
    for cid, k in zip(harness.W.chunk_ids(len(chunks)), keys):
        eng.store.attach_index_keys(cid, k)
    with open(pathlib.Path(__file__).with_suffix(".calls"), "a") as f:
        f.write(json.dumps({"engine": id(eng), "seed": seed,
                            "selector": type(eng.selector).__name__,
                            "keys_sum": float(keys.sum())}) + "\n")
    return eng
