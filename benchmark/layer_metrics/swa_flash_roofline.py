"""Pallas kernels: the flash-attention kernels of the sliding-window layers
(grouped keys and values, one softmax a layer), their share of their
roofline, in percent: the least time the chip could take for the executions
traced (per execution the larger of FLOPs over the bf16 peak and bytes over
the HBM peak) over the time they took.

The kernels are found as `diff_flash_roofline` finds them: by signature and
by the shape the family's `flash_kernel_shapes` gives, the windowed layers'
by the `attn.window` scope of `models/transformer.py` (in the instruction's
`op_name`, or in its name). Their work is the BAND's: the score entries a
query's last `window` keys hold, fewer at the sequence's start (the family's
`keys_seen`), q.k and p.v at the head's width, keys and values moved once a
group of query heads. `part` is what the four readers of a model with one
full layer to several windowed ones share: `swa_flash_*` read the "window"
part, `full_flash_*` the "full" one. None for a program without an `attn.*`
scope or a family without `flash_kernel_shapes`."""

from benchmark.harness.runner import say
from benchmark.layer_metrics import diff_flash_roofline


def part(run, which: str):
    """(ms a step the flash kernels of the `which` layers took, the least
    they could take, how many such layers) of a traced run; None where the
    program, the family or the trace has nothing to read."""
    took, least = (diff_flash_roofline.parts(run) or {}).get(which,
                                                             (0.0, 0.0))
    if not took:
        return None
    layers = run.family.flash_kernel_shapes(
        run.cell.config, run.cell.traffic)["layers"][which][0]
    return took, least, layers


def read_ms(run, which: str, metric: str):
    """The `which` layers' kernels' ms a step, with a line in the run's
    log."""
    found = part(run, which)
    if found is None:
        return None
    took, least, layers = found
    say(f"{run.cell.name}: {metric}: {took:.3f} ms over {layers} layer(s), "
        f"{took / max(layers, 1):.3f} a layer (least {least:.3f})")
    return took


def read_share(run, which: str):
    found = part(run, which)
    if found is None or run.peaks is None:
        return None
    took, least, _ = found
    return 100.0 * least / took


def read(run):
    return read_share(run, "window")
