"""Test hooks: a reference that reads 1.0 above the plain one, so every
sampled output lies over the limit."""

import reference


def expected(config, seed, request, step, chunk_ids, chunk_arrays, dtype):
    return reference.expected(config, seed, request, step, chunk_ids,
                              chunk_arrays, dtype) + 1.0
