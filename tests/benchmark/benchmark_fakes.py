"""Hand-made inputs with known answers for the benchmark's own tests."""

from types import SimpleNamespace

US = 1_000_000  # picoseconds in a microsecond

#: A compiled program's text in miniature: one fusion, an asynchronous
#: all-reduce, a Mosaic kernel with the flash forward's signature, an add.
HLO_TEXT = """
HloModule jit_step, entry_computation_layout={(bf16[8,128]{1,0})->bf16[8,128]{1,0}}

%fused_computation (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %mul.7 = bf16[8,128]{1,0:T(8,128)(2,1)} multiply(%p, %p)
}

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation
  %all-reduce-start.1 = (bf16[8,128]{1,0:T(8,128)(2,1)}, f32[16]{0:T(256)}) all-reduce-start(%fusion.1, %c), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
  %all-reduce-done.1 = (bf16[8,128]{1,0:T(8,128)(2,1)}, f32[16]{0:T(256)}) all-reduce-done(%all-reduce-start.1)
  %jvp__.1 = (bf16[2,64,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[2,64,1]{2,1,0:T(8,128)S(1)}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,64,128]{2,1,0}, bf16[2,64,128]{2,1,0}}
  %transpose_jvp___.2 = (bf16[2,64,128]{2,1,0}, bf16[2,64,128]{2,1,0}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %transpose_jvp___.3 = bf16[2,64,128]{2,1,0} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %other.4 = f32[4]{0} custom-call(%a), custom_call_target="SomethingElse"
  ROOT %add.1 = bf16[8,128]{1,0:T(8,128)(2,1)} add(%fusion.1, %fusion.1)
}
"""

STEPS = 5            # executions of the step program in the hand-made trace
PERIOD = 100         # microseconds from one start to the next

#: per step, relative to its start, in microseconds: (name, start, duration)
STEP_OPS = (("%while.9 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while(%tuple.1), "
             "condition=%cond, body=%body", 0, 95),   # spans its body's ops
            ("%fusion.1 = bf16[8,128]{1,0} fusion(%a)", 0, 30),
            ("all-reduce-start.1", 20, 5),     # inside fusion.1: hidden
            ("jvp__.1", 40, 20),
            ("all-reduce-done.1", 60, 10),
            ("add.1", 90, 5))                  # in the second, small program
STEP_MODULES = (("jit_step(123456)", 0, 80), ("jit_add(77)", 90, 5))
STEP_SPANS = (("bench.step", 0, 100), ("bench.spmd_step", 28, 17),
              ("bench.block", 65, 34), ("not.ours", 0, 100))


def _line(name, events, ids):
    rows = "".join(
        f"    events {{ metadata_id: {ids.setdefault(n, len(ids) + 1)} "
        f"offset_ps: {int(start * US)} duration_ps: {int(dur * US)} }}\n"
        for n, start, dur in events)
    return (f'  lines {{ id: {abs(hash(name)) % 1000 + 1} name: "{name}" '
            f"timestamp_ns: 0\n{rows}  }}\n")


def _plane(plane_id, name, lines):
    ids = {}
    body = "".join(_line(line, events, ids) for line, events in lines)
    meta = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    return f'planes {{\n  id: {plane_id} name: "{name}"\n{body}{meta}}}\n'


def hand_made_xspace(devices: int = 2) -> str:
    """A text-format XSpace: `devices` chips running the same `STEPS` steps
    (the second chip's ops start 1 us later), one host plane with the
    benchmark's spans, and planes the reduction must ignore."""
    def repeat(rows, shift=0):
        return [(n, step * PERIOD + start + shift, dur)
                for step in range(STEPS) for n, start, dur in rows]

    planes = [_plane(i + 1, f"/device:TPU:{i}",
                     [("XLA Modules", repeat(STEP_MODULES, i)),
                      ("XLA Ops", repeat(STEP_OPS, i)),
                      ("Steps", repeat((("7", 0, 100),)))])
              for i in range(devices)]
    planes.append(_plane(10, "/device:TPU:0 SparseCore 0",
                         [("XLA Ops", repeat((("noise", 0, 100),)))]))
    planes.append(_plane(11, "/host:CPU",
                         [("python3", repeat(STEP_SPANS))]))
    return "".join(planes)


def fake_run(trace, instructions, **more):
    """What a metric reader reads, with only the fields given."""
    return SimpleNamespace(trace=trace, instructions=instructions, **more)
