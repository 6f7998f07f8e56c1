"""Test hooks: a misspelt hook, which must fail set-up rather than fall
back to the harness's own FLOP count."""

import harness


def step_flop(requests, config):
    return harness.step_flops(requests, config)
