"""Collectives across chips: bytes the step program all-reduces per
execution, per chip, from the result shapes in its compiled text."""

from benchmark.harness import hlo


def read(run):
    if not run.instructions:
        return None
    return hlo.allreduce_bytes(run.instructions)
