"""Expert layer: the grouped matmuls' share of their roofline, in percent:
the least time the chip could take for the executions traced (per execution
the larger of FLOPs over the bf16 peak and bytes over the HBM peak, from the
shapes) over the time they and their metadata kernels took.

Every grouped matmul of a step has the same work: the forward's three
products (gate, up, down), their repeat under remat, and the backward's two
per product (towards the rows and towards the weights) each multiply the
same (rows x hidden x expert width) once."""

from benchmark.harness import scopes, xplane


def grouped_matmul_work(shape, elem_bytes: int = 2):
    """(FLOPs, bytes) of one grouped matmul over `rows` rows sorted by
    expert, (rows, hidden) x (experts, hidden, width) or either of its
    transposes: 2 FLOPs per multiply-add; the rows, all experts' weights and
    the result each moved once."""
    rows, hidden, width, experts = shape
    flops = 2 * rows * hidden * width
    moved = (rows * hidden + experts * hidden * width + rows * width) \
        * elem_bytes
    return flops, moved


def least_seconds(shape, peaks):
    """(seconds, which bound holds) for one execution."""
    flops, moved = grouped_matmul_work(shape)
    compute, memory = flops / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s
    return max(compute, memory), "compute" if compute >= memory else "memory"


def read(run):
    if scopes.traced_parts(run) is None or run.peaks is None \
            or not hasattr(run.family, "grouped_matmul_shape"):
        return None
    shape = run.family.grouped_matmul_shape(run.cell.config,
                                            run.cell.traffic)
    dev = run.trace.devices[0]
    kernels = scopes.grouped_kernels(run.instructions)
    executions = xplane.op_counts_per_step(
        dev, lambda name: kernels.get(name) == scopes.GROUPED_MATMUL)
    took = xplane.op_seconds_per_step(dev, kernels.__contains__)
    if not executions or not took:
        return None
    return 100.0 * executions * least_seconds(shape, run.peaks)[0] / took
