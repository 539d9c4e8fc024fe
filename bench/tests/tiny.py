"""A benchmark cell cut to the registry's smoke widths, for CPU tests.

The cell keeps its traffic's path (mesh, overlap, remat, optimizer, check
steps) and its limits, at a sequence of 64 tokens, and the model takes the
smoke configuration of its registry entry.  ``patched_registry`` makes the
harness's registry lookup return that smoke configuration.
"""

from __future__ import annotations

import contextlib
import copy

from bench import spec


def tiny_cell(name: str, seq: int = 64, limits_of: str = ""):
    """``limits_of`` names the listed cell whose limits an unlisted one
    takes."""
    import repro.config as RC

    c = spec.cell(name) if not limits_of else spec.unlisted_cell(name)
    conf = copy.deepcopy(c.config)
    smoke = RC.get_smoke_config(conf["registry"])
    conf["program"].update(
        num_layers=smoke.num_layers, d_model=smoke.d_model,
        num_heads=smoke.num_heads, num_kv_heads=smoke.num_kv_heads,
        head_dim=smoke.resolved_head_dim, d_ff=smoke.d_ff,
        vocab_size=smoke.vocab_size)
    for field, key in conf["program_from"].items():
        conf[key] = conf["program"][field]
    traffic = copy.deepcopy(c.traffic)
    traffic["seq_len"] = seq
    limits = spec.cell(limits_of).limits if limits_of else c.limits
    cell = spec.Cell(c.name, conf, traffic, limits, c.chips, c.end_to_end,
                     c.per_layer)
    return cell, smoke


@contextlib.contextmanager
def patched_registry(smoke):
    import repro.config as RC

    real = RC.get_config
    RC.get_config = lambda name: smoke
    try:
        yield
    finally:
        RC.get_config = real
