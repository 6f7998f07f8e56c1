"""Test hooks: a step that counts twice the dense attention FLOPs."""

import harness


def step_flops(requests, config):
    return 2 * harness.step_flops(requests, config)
