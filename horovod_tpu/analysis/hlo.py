"""hvdhlo: structural analysis of the lowered XLA step program.

hvdlint (PR 3-4) sees Python source; the perf properties the ROADMAP
cares about — gradient-comms overlap, buffer donation, layout padding,
host round-trips — are properties of the *lowered program* and invisible
to an AST linter. This module parses the two textual forms the toolchain
already produces for free and hands a uniform op/def-use model to the
HVD2xx rules (``analysis/hlo_rules.py``):

* **StableHLO MLIR** — ``jax.jit(f).lower(*args).as_text()``, the cheap
  pre-optimization form bench and perfscope already lower for cost
  analysis. Donation shows up as ``jax.buffer_donor``/
  ``tf.aliasing_output`` argument attributes.
* **HLO text** — ``lowered.compile().as_text()`` or a dumped
  ``*.before_optimizations.txt`` module. Donation shows up in the
  module-level ``input_output_alias`` map.

The parser is deliberately line-structural, not a grammar: it recovers
(result, opcode, operands, operand/result tensor types, attribute text)
per instruction plus entry parameters and their donation bits — exactly
what the rules consume — and ignores everything else. A formatting
drift in a field no rule reads therefore cannot break the lint.

Findings ride the existing driver machinery (`driver.Finding`,
``file:line RULE-ID msg``, ``--format json``, ``--baseline``); there are
no source comments in lowered text, so HLO findings are silenced via the
baseline file (``scripts/hvdhlo_baseline.json``), not inline
suppressions. Findings feed ``hvdhlo_findings_total{rule}``
(docs/observability.md). See docs/static_analysis.md for the rule
catalog and docs/perf.md for the CI gate (``make hlo-lint``).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from horovod_tpu.analysis.driver import Finding

#: Bytes per element for the dtypes XLA prints. Unknown dtypes parse to
#: itemsize None and size-based rules skip the value instead of guessing.
DTYPE_BYTES = {
    "pred": 1, "i1": 1, "s8": 1, "u8": 1, "i8": 1, "ui8": 1,
    "s16": 2, "u16": 2, "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "i32": 4, "ui32": 4, "f32": 4,
    "s64": 8, "u64": 8, "i64": 8, "ui64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
}


@dataclasses.dataclass(frozen=True)
class TensorType:
    """One tensor type: dtype token + static dims (None on dynamic)."""

    dtype: str
    dims: Tuple[int, ...]

    @property
    def elems(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def itemsize(self) -> Optional[int]:
        return DTYPE_BYTES.get(self.dtype.lower())

    @property
    def nbytes(self) -> Optional[int]:
        i = self.itemsize
        return None if i is None else self.elems * i

    def __str__(self) -> str:
        dims = "x".join(str(d) for d in self.dims)
        return f"{self.dtype}[{dims}]" if self.dims else f"{self.dtype}[]"


@dataclasses.dataclass
class HloOp:
    """One instruction, normalized across the two textual forms."""

    line: int                     # 1-based line in the analyzed text
    result: str                   # "%23" ("" for results-less ops)
    opcode: str                   # canonical: all_reduce, dot_general, ...
    operands: Tuple[str, ...]     # SSA names, '#i' projections stripped
    operand_types: Tuple[Optional[TensorType], ...]
    result_types: Tuple[Optional[TensorType], ...]
    attrs: str                    # raw remainder text for attr regexes
    scope: str                    # enclosing function / computation name
    #: Scalar value of a ``constant`` op's literal (both textual forms,
    #: incl. scientific notation, typed ``bf16[] 8`` spellings and MLIR
    #: ``dense<>`` splats); None for non-constants and non-scalars.
    literal: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class HloParam:
    """One computation parameter (entry, `call`ee, or shard_map body —
    sharding/donation attrs are recorded uniformly at every function
    boundary, not just the entry signature)."""

    index: int
    name: str                     # "%arg0" / "%p.1"
    type: Optional[TensorType]
    donated: bool
    scope: str
    line: int
    #: Raw sharding annotation text ("{replicated}",
    #: "{devices=[2,4]<=[8]}", ...) or None when unannotated. The
    #: sharding-aware layer (analysis/shard.py) interprets it.
    sharding: Optional[str] = None


class HloProgram:
    """Parsed module: op list + def/use indexes the rules query."""

    def __init__(self, path: str, ops: List[HloOp],
                 params: List[HloParam], entry_scope: str,
                 fmt: str, num_partitions: int = 1) -> None:
        self.path = path
        self.ops = ops
        self.params = params
        self.entry_scope = entry_scope
        self.fmt = fmt  # "stablehlo" | "hlo"
        #: SPMD partition count (mhlo.num_partitions module attr /
        #: HloModule header); 1 for unpartitioned programs.
        self.num_partitions = num_partitions
        self._defs: Dict[Tuple[str, str], HloOp] = {}
        self._uses: Dict[Tuple[str, str], List[HloOp]] = {}
        for op in ops:
            if op.result:
                self._defs.setdefault((op.scope, op.result), op)
            for o in op.operands:
                self._uses.setdefault((op.scope, o), []).append(op)

    @property
    def entry_params(self) -> List[HloParam]:
        return [p for p in self.params if p.scope == self.entry_scope]

    def defining(self, scope: str, name: str) -> Optional[HloOp]:
        return self._defs.get((scope, name))

    def uses(self, scope: str, name: str) -> List[HloOp]:
        return self._uses.get((scope, name), [])

    def depends_on(self, op: HloOp, target: HloOp,
                   max_visits: int = 4096) -> bool:
        """True when `op` transitively consumes `target`'s result
        (same-scope def-use reachability; the overlap-chain query)."""
        if op.scope != target.scope or not target.result:
            return False
        seen: Set[str] = set()
        frontier = list(op.operands)
        visits = 0
        while frontier and visits < max_visits:
            name = frontier.pop()
            if name in seen:
                continue
            seen.add(name)
            visits += 1
            if name == target.result:
                return True
            d = self.defining(op.scope, name)
            if d is not None:
                frontier.extend(d.operands)
        return False


# ------------------------------------------------------------- parsing

_TENSOR_RE = re.compile(r"tensor<([^<>]*?)>")
_HLO_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\]")
_SSA_RE = re.compile(r"%[\w.-]+")


def _parse_mlir_tensor(inner: str) -> Optional[TensorType]:
    """``2x8x8x64xbf16`` / ``f32`` / ``?x128xf32`` -> TensorType|None."""
    parts = inner.split("x")
    dims: List[int] = []
    for i, p in enumerate(parts):
        p = p.strip()
        if p.isdigit():
            dims.append(int(p))
            continue
        if p == "?":
            return None  # dynamic: size-based rules must skip
        dtype = "x".join(parts[i:]).strip()
        # complex<f32> etc. keep their full token; lookup just misses.
        return TensorType(dtype, tuple(dims))
    return None


def _mlir_types(segment: str) -> List[Optional[TensorType]]:
    """Every tensor<> type in `segment`, in order (non-tensor -> None
    is NOT emitted; callers align by count only when it matches)."""
    return [_parse_mlir_tensor(m.group(1))
            for m in _TENSOR_RE.finditer(segment)]


def _hlo_types(segment: str) -> List[Optional[TensorType]]:
    return [TensorType(m.group(1),
                       tuple(int(d) for d in m.group(2).split(",") if d))
            for m in _HLO_SHAPE_RE.finditer(segment)]


def _operand_names(segment: str) -> Tuple[str, ...]:
    return tuple(m.group(0).split("#")[0]
                 for m in _SSA_RE.finditer(segment))


# Constant literals, both textual forms. XLA prints scalars plain
# (``constant(8)``), in scientific notation (``constant(1.25e-05)``)
# and — for the narrow dtypes — typed (``constant(bf16[] 8)``,
# ``constant(f8e4m3fn[] 1.5e-2)``); StableHLO prints ``dense<>`` attrs
# (``dense<1.250000e-01>``). The number grammar must cover all of them:
# a literal the parser cannot read is a silently skipped operand, and
# the HVD503 divisor extraction then misses the baked scale constant.
_LITERAL_NUM_RE = re.compile(
    r"[-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?")
_MLIR_DENSE_RE = re.compile(r"dense<(.*)>\s*$", re.DOTALL)


def parse_literal(text: str) -> Optional[float]:
    """Scalar value of one constant literal, or None when the literal
    is non-scalar (array/tuple braces, hex-encoded dense blobs) — the
    caller must then skip the value rather than guess."""
    s = text.strip()
    m = _MLIR_DENSE_RE.match(s)
    if m:
        s = m.group(1).strip()
    # typed scalar literal: a leading `dtype[]` token before the value
    tm = _HLO_SHAPE_RE.match(s)
    if tm and tm.start() == 0:
        if tm.group(2).strip():
            return None  # shaped literal: `f32[2] {1, 2}` is not scalar
        s = s[tm.end():].strip()
    if not s or s[0] in "{[\"":
        return None  # array / tuple / hex-string literal
    low = s.lower()
    if low in ("true", "false"):
        return 1.0 if low == "true" else 0.0
    if low in ("inf", "+inf", "-inf", "nan"):
        return float(low)
    if _LITERAL_NUM_RE.fullmatch(s):
        return float(s)
    return None


def constant_value(op: "HloOp") -> Optional[float]:
    """The scalar a ``constant`` op defines; None for anything else.
    The HVD503 gradient-scale rules resolve explicit divide/multiply
    scale factors through this accessor."""
    return op.literal if op.opcode == "constant" else None


# StableHLO op header: `%23 = "stablehlo.all_reduce"(%22) <{...}> ({`
# or `%0 = stablehlo.dot_general %arg0, %arg1, ... : (T, T) -> T`
# or `stablehlo.return %25 : tensor<f32>` / `return %1 : tensor<...>`.
_MLIR_OP_RE = re.compile(
    r"^\s*(?:(%[\w]+)(?::\d+)?\s*=\s*)?"
    r'"?([a-zA-Z_][\w$]*\.)?([a-zA-Z_][\w$-]*)"?\s*(?=[ (%<"@]|$)')
_MLIR_FUNC_RE = re.compile(
    r"^\s*func\.func\s+(?:(public|private)\s+)?@([\w$-]+)\s*\((.*)$")
# The attr dict may nest braces two levels (mhlo.sharding strings like
# {jax.buffer_donor = true, mhlo.sharding = "{devices=[2,4]<=[8]
# last_tile_dims={replicated}}"}) — the donation bit and the sharding
# string must both survive riding alongside each other.
_MLIR_ARG_RE = re.compile(
    r"(%arg\d+):\s*"
    r"([^,){]+(?:\{(?:[^{}]|\{(?:[^{}]|\{[^{}]*\})*\})*\})?)")
_MLIR_SHARDING_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_MLIR_NUM_PARTITIONS_RE = re.compile(
    r"mhlo\.num_partitions\s*=\s*(\d+)")
# HLO text: `sharding={devices=[4,1,2]<=[2,4]T(1,0)
# last_tile_dim_replicate}` / `sharding={replicated}` instruction attr
# (entry parameters keep their annotation through SPMD partitioning).
_HLO_SHARDING_RE = re.compile(
    r"sharding=(\{(?:[^{}]|\{[^{}]*\})*\})")
_HLO_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)")


def op_sharding(op: HloOp) -> Optional[str]:
    """The raw sharding annotation carried by one instruction, for BOTH
    textual forms: ``mhlo.sharding = "..."`` on a StableHLO custom-call
    (`@Sharding` = `with_sharding_constraint`), ``sharding={...}`` on an
    HLO-text instruction. None when the op is unannotated."""
    m = _MLIR_SHARDING_RE.search(op.attrs)
    if m:
        return m.group(1)
    m = _HLO_SHARDING_RE.search(op.attrs)
    return m.group(1) if m else None

#: MLIR keywords the op regex would otherwise read as opcodes.
_MLIR_NOISE = {"module", "func", "}", "{", "^bb0", "cond", "do"}


def _parse_stablehlo(text: str, path: str) -> HloProgram:
    ops: List[HloOp] = []
    params: List[HloParam] = []
    entry_scope = ""
    scope = ""
    num_partitions = 1
    # stack of (op, brace_balance_at_open) for region ops whose result
    # type arrives on the closing `}) : (...) -> ...` line
    pending: List[HloOp] = []
    lines = text.splitlines()
    for lineno, raw in enumerate(lines, 1):
        line = raw.strip()
        if not line or line.startswith("//"):
            continue
        if line.startswith("module"):
            pm = _MLIR_NUM_PARTITIONS_RE.search(line)
            if pm:
                num_partitions = int(pm.group(1))
            continue
        fm = _MLIR_FUNC_RE.match(raw)
        if fm:
            vis, name, argtext = fm.group(1), fm.group(2), fm.group(3)
            scope = name
            if vis == "public" or (not entry_scope and name == "main"):
                entry_scope = name
            for i, am in enumerate(_MLIR_ARG_RE.finditer(argtext)):
                arg, typetext = am.group(1), am.group(2)
                types = _mlir_types(typetext)
                donated = ("jax.buffer_donor" in typetext
                           or "tf.aliasing_output" in typetext)
                sm = _MLIR_SHARDING_RE.search(typetext)
                params.append(HloParam(i, arg, types[0] if types else None,
                                       donated, scope, lineno,
                                       sm.group(1) if sm else None))
            continue
        if line.startswith("})"):
            # close of a region op: its functional type rides here
            _, _, typesig = line.partition(":")
            if pending:
                op = pending.pop()
                ins, _, outs = typesig.partition("->")
                op.operand_types = tuple(_mlir_types(ins))
                op.result_types = tuple(_mlir_types(outs))
            continue
        m = _MLIR_OP_RE.match(raw)
        if not m:
            continue
        result = m.group(1) or ""
        opcode = m.group(3)
        if opcode in _MLIR_NOISE or line.startswith("^"):
            continue
        opcode = opcode.replace("-", "_")
        rest = raw[m.end():]
        # the trailing ` : type` annotation (absent on region openers)
        body, _, typesig = rest.rpartition(" : ")
        if not body:
            body, typesig = rest, ""
        operand_types: Tuple[Optional[TensorType], ...] = ()
        result_types: Tuple[Optional[TensorType], ...] = ()
        if "->" in typesig:
            ins, _, outs = typesig.partition("->")
            operand_types = tuple(_mlir_types(ins))
            result_types = tuple(_mlir_types(outs))
        elif typesig:
            result_types = tuple(_mlir_types(typesig))
        op = HloOp(lineno, result, opcode, _operand_names(body),
                   operand_types, result_types, rest.strip(), scope,
                   parse_literal(body) if opcode == "constant" else None)
        ops.append(op)
        # `({` with no matching `})` on the same line opens a region
        if rest.count("({") > rest.count("})"):
            pending.append(op)
    return HloProgram(path, ops, params, entry_scope or "main",
                      "stablehlo", num_partitions)


# HLO text: `  %all-reduce.2 = f32[256,256]{1,0} all-reduce(f32[...] %x),
# channel_id=1, ...` inside `ENTRY %main ... {` ... `}` computations.
# XLA prints the `%` name sigil in some modes and omits it in others;
# both spellings are accepted.
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.-]+)\s*=\s*(.+?)\s([a-z][a-z0-9-]*)\((.*)$")
_HLO_COMP_RE = re.compile(
    r"^\s*(ENTRY\s+)?(%?[\w.-]+)\s.*->\s.*\{\s*$")
_HLO_ALIAS_RE = re.compile(
    r"input_output_alias=\{([^{}]*(?:\{[^{}]*\}[^{}]*)*)\}")


def _hlo_alias_params(header: str) -> Set[int]:
    """Donated parameter numbers from the module-level alias map:
    ``{0}: (0, {}, may-alias)`` -> param 0."""
    m = _HLO_ALIAS_RE.search(header)
    if not m:
        return set()
    return {int(g) for g in re.findall(r"\(\s*(\d+)\s*,", m.group(1))}


def _split_args(segment: str) -> Tuple[str, str]:
    """(arg list, attr remainder) of an instruction tail, honoring
    nested parens: ``f32[2]{0} %a, %b), channel_id=1`` splits at the
    close paren matching the opcode's open."""
    depth = 0
    for i, ch in enumerate(segment):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if ch == ")" and depth == 0:
                return segment[:i], segment[i + 1:]
            depth -= 1
    return segment, ""


def _parse_hlo_text(text: str, path: str) -> HloProgram:
    ops: List[HloOp] = []
    params: List[HloParam] = []
    entry_scope = ""
    scope = ""
    in_entry = False
    donated: Set[int] = set()
    num_partitions = 1
    lines = text.splitlines()
    for lineno, raw in enumerate(lines, 1):
        if raw.startswith("HloModule"):
            donated = _hlo_alias_params(raw)
            pm = _HLO_NUM_PARTITIONS_RE.search(raw)
            if pm:
                num_partitions = int(pm.group(1))
            continue
        im = _HLO_INSTR_RE.match(raw)
        if im:
            result, typetext, opcode, tail = im.groups()
            args, attrs = _split_args(tail)
            opcode = opcode.replace("-", "_")
            op = HloOp(lineno, result, opcode, _operand_names(args),
                       tuple(_hlo_types(args)), tuple(_hlo_types(typetext)),
                       attrs.strip(", "), scope,
                       parse_literal(args) if opcode == "constant"
                       else None)
            ops.append(op)
            if opcode == "parameter":
                pm = re.match(r"\s*(\d+)", args)
                idx = int(pm.group(1)) if pm else len(params)
                params.append(HloParam(
                    idx, result, op.result_types[0] if op.result_types
                    else None, in_entry and idx in donated, scope, lineno,
                    op_sharding(op)))
            continue
        cm = _HLO_COMP_RE.match(raw)
        if cm and "=" not in raw.split("->")[0]:
            in_entry = bool(cm.group(1))
            scope = cm.group(2)
            if in_entry:
                entry_scope = scope
    # parameters of non-entry computations are never donation candidates
    # (only the entry alias map carries donation bits), but they DO keep
    # their sharding attrs — call/shard_map boundaries are recorded
    # uniformly with the entry signature.
    return HloProgram(path, ops, params, entry_scope, "hlo",
                      num_partitions)


def parse(text: str, path: str = "<hlo>") -> HloProgram:
    """Parse either textual form; dispatch by content."""
    head = text[:4096]
    if "HloModule" in head:
        return _parse_hlo_text(text, path)
    return _parse_stablehlo(text, path)


# ------------------------------------------------------------- linting

def registry() -> Dict[str, Tuple[str, object]]:
    """rule_id -> (description, check(program) -> iterable[Finding])."""
    from horovod_tpu.analysis import hlo_rules
    return dict(hlo_rules.RULES)


def lint_text(text: str, path: str = "<hlo>",
              select: Optional[Sequence[str]] = None,
              ignore: Sequence[str] = ()) -> List[Finding]:
    """Run the HVD2xx rules over one lowered module's text."""
    prog = parse(text, path)
    wanted = {r.upper() for r in select} if select is not None else None
    ignored = {r.upper() for r in ignore}
    out: List[Finding] = []
    for rule_id, (_desc, check) in sorted(registry().items()):
        if wanted is not None and rule_id not in wanted:
            continue
        if rule_id in ignored:
            continue
        out.extend(check(prog))
    out.sort(key=lambda f: (f.line, f.rule_id))
    return out


def lint_files(paths: Sequence[str],
               select: Optional[Sequence[str]] = None,
               ignore: Sequence[str] = ()) -> List[Finding]:
    """Lint dumped modules; unreadable paths fail the gate (HVD999),
    mirroring the AST driver's contract."""
    findings: List[Finding] = []
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as fh:
                text = fh.read()
        except OSError as e:
            findings.append(Finding(str(p), 1, "HVD999",
                                    f"unreadable: {e}"))
            continue
        findings.extend(lint_text(text, path=str(p), select=select,
                                  ignore=ignore))
    findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
    return findings


def lint_enabled() -> bool:
    """HOROVOD_HLO_LINT gate (default on) for the bench-side stamping;
    the CLI/CI path runs unconditionally."""
    from horovod_tpu.common.config import _env_on
    return _env_on("HOROVOD_HLO_LINT", True)


#: Bench stamps at most this many findings per section (full details
#: always come from re-running the CLI on the dumped module).
_SUMMARY_MAX_FINDINGS = 20


def lint_summary(text: str, path: str = "<lowered>") -> Dict[str, object]:
    """The compact per-section stamp bench embeds in its JSON line."""
    findings = lint_text(text, path=path)
    record_metrics(findings)
    rules: Dict[str, int] = {}
    for f in findings:
        rules[f.rule_id] = rules.get(f.rule_id, 0) + 1
    out: Dict[str, object] = {"count": len(findings),
                              "clean": not findings}
    if findings:
        out["rules"] = rules
        out["findings"] = [f.render()
                           for f in findings[:_SUMMARY_MAX_FINDINGS]]
        if len(findings) > _SUMMARY_MAX_FINDINGS:
            out["truncated"] = len(findings) - _SUMMARY_MAX_FINDINGS
    return out


def record_metrics(findings: Sequence[Finding]) -> None:
    """hvdhlo_findings_total{rule} (PR 2 registry); lint must work in
    environments without the runtime deps, so failures are swallowed."""
    try:
        from horovod_tpu.observability import metrics as m
        counter = m.registry().counter(
            "hvdhlo_findings_total", "hvdhlo findings by rule",
            labelnames=("rule",))
        for f in findings:
            counter.labels(rule=f.rule_id).inc()
    except Exception:
        pass


# ------------------------------------------------- canonical step lower

def _force_cpu_mesh(min_devices: int = 2):
    """CPU backend with a multi-device virtual mesh (the conftest
    recipe). The lint lowers on the CPU by design — it needs no chip and
    must not take one — so the platform is set here, not left to the
    environment."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < min_devices:
        raise RuntimeError(
            f"hlo-lint needs >= {min_devices} CPU devices; the backend "
            "initialized before the device-count flag could apply "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before starting python)")
    return jax


def lower_step_text(kind: str = "lm") -> str:
    """StableHLO text of the canonical DP train step under the CURRENT
    fusion config — the program `make hlo-lint` gates.

    `lm`: the tied-embedding transformer-LM shape from bench's
    lm_overlap section (an 8 MB embedding + 6 residual FFN blocks,
    ~25 MB of f32 gradients) through the framework's own in-jit
    bucketed reduction on the virtual CPU mesh. The 8 MB embedding
    gradient is the canary: with chunking + the bucket cap intact every
    all-reduce payload stays <= the cap; reverting ops/fusion.py to the
    pre-PR-6 single-giant-allreduce plan (or lifting the cap while
    raising the threshold) resurfaces a >cap payload and trips HVD201.
    """
    if kind == "resnet_block":
        return _resnet_block_step_text()
    if kind != "lm":
        raise ValueError(f"unknown --hlo-step program {kind!r}")
    jax = _force_cpu_mesh()
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.common import config as C
    from horovod_tpu.ops import fusion
    from horovod_tpu.optim.optimizer import reduce_gradients_in_jit

    # The env-derived effective threshold, computed here rather than
    # through topology state so the gate needs no hvd.init(): both an
    # env simulation of the old plan (HOROVOD_FUSION_THRESHOLD=64MB +
    # HOROVOD_BUCKET_CAP=0) and a code revert of the chunking land in
    # the lowered program.
    thresh = fusion.effective_threshold(
        C._env_int(C.HOROVOD_FUSION_THRESHOLD,
                   C.DEFAULT_FUSION_THRESHOLD_BYTES),
        C._env_int(C.HOROVOD_BUCKET_CAP, C.DEFAULT_BUCKET_CAP_BYTES))

    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(ndev), ("hvd",))
    rng = np.random.default_rng(0)
    D, F, V, NL = 256, 1024, 8192, 6
    params = {"emb": jnp.asarray(
        rng.standard_normal((V, D)) * 0.02, jnp.float32)}
    for i in range(NL):
        params[f"wi{i}"] = jnp.asarray(
            rng.standard_normal((D, F)) * 0.02, jnp.float32)
        params[f"wo{i}"] = jnp.asarray(
            rng.standard_normal((F, D)) * 0.02, jnp.float32)

    def local_step(p, tok, tgt):
        def loss(p):
            h = p["emb"][tok]
            for i in range(NL):
                h = h + jnp.tanh(h @ p[f"wi{i}"]) @ p[f"wo{i}"]
            logits = h @ p["emb"].T
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

        g = jax.grad(loss)(p)
        g = reduce_gradients_in_jit(g, num_ranks=ndev,
                                    fusion_threshold_bytes=thresh)
        return jax.tree_util.tree_map(lambda a, b: a - 0.01 * b, p, g)

    B, S = 16, 64
    tok = jnp.asarray(rng.integers(0, V, (B * ndev, S)))
    tgt = jnp.roll(tok, -1, axis=1)
    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P("hvd"), P("hvd")), out_specs=P(),
                         check_vma=False)
    return jax.jit(step, donate_argnums=0).lower(params, tok, tgt).as_text()


def _resnet_block_step_text() -> str:
    """StableHLO text of a C=64 ResNet bottleneck-block train step under
    the CURRENT layout config — the `make conv-smoke` gate.

    The block is the live twin of the checked-in
    ``hvd204_resnet_block`` fixture (stage-0 shape: trunk 64, width 64
    — every conv channel dim at 50% MXU padding waste, the exact
    HVD204 canary). The layout pass (ops/layout.py) pads the declared
    stack to the 128-lane width before lowering, so the DEFAULT config
    lints clean; reverting the pass (HOROVOD_LAYOUT_PAD=0, or a
    regression in plan()/pad()) resurfaces the unaligned dims and
    trips HVD204 — pinned both ways by tests/test_hvdhlo.py.
    """
    jax = _force_cpu_mesh()
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import layout as L
    from horovod_tpu.ops.layout import Site

    C, W = 64, 64  # stage-0 trunk/width: the 50%-waste fixture shape
    rng = np.random.default_rng(0)

    def conv_init(kh, kw, cin, cout):
        return jnp.asarray(
            rng.standard_normal((kh, kw, cin, cout))
            * (2.0 / (kh * kw * cin)) ** 0.5, jnp.float32)

    def bn_init(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    params = {"conv1": conv_init(1, 1, C, W), "bn1": bn_init(W),
              "conv2": conv_init(3, 3, W, W), "bn2": bn_init(W),
              "conv3": conv_init(1, 1, W, 4 * W), "bn3": bn_init(4 * W),
              "proj": conv_init(1, 1, C, 4 * W), "bnp": bn_init(4 * W),
              "fc": jnp.asarray(rng.standard_normal((4 * W, 1000))
                                * (4 * W) ** -0.5, jnp.float32)}
    stack = [Site("conv1", {2: "in", 3: "c1"}),
             Site("bn1/scale", {0: "c1"}), Site("bn1/bias", {0: "c1"}),
             Site("conv2", {2: "c1", 3: "c2"}),
             Site("bn2/scale", {0: "c2"}), Site("bn2/bias", {0: "c2"}),
             Site("conv3", {2: "c2", 3: "out"}),
             Site("bn3/scale", {0: "out"}), Site("bn3/bias", {0: "out"}),
             Site("proj", {2: "in", 3: "out"}),
             Site("bnp/scale", {0: "out"}), Site("bnp/bias", {0: "out"}),
             Site("fc", {0: "out"})]
    plan = L.plan(params, stack)
    params = plan.pad(params)
    cin = plan.edges["in"].padded  # activations enter on the padded trunk

    def bn(x, p):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
        inv = jax.lax.rsqrt(var + 1e-5)
        return (x - mean) * inv * p["scale"] + p["bias"]

    def conv(x, w, stride=1):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def loss(p, x, yl):
        h = jax.nn.relu(bn(conv(x, p["conv1"]), p["bn1"]))
        h = jax.nn.relu(bn(conv(h, p["conv2"]), p["bn2"]))
        h = bn(conv(h, p["conv3"]), p["bn3"])
        sc = bn(conv(x, p["proj"]), p["bnp"])
        h = jnp.mean(jax.nn.relu(h + sc), axis=(1, 2))
        logp = jax.nn.log_softmax(h @ p["fc"])
        return -jnp.mean(jnp.take_along_axis(logp, yl[:, None], axis=1))

    def step(p, x, yl):
        g = jax.grad(loss)(p, x, yl)
        return jax.tree_util.tree_map(lambda a, b: a - 0.01 * b, p, g)

    # Bench-canonical batch and class count: the BACKWARD contracts over
    # the batch (conv dW) and the classes (softmax dlogits), so an
    # unaligned batch would self-inflict the very HVD204 findings this
    # program exists to prove the LAYOUT pass removes. B=128 is the
    # measured conv sweet spot (docs/benchmarks.md); 1000 classes sits
    # under the padding-waste floor, exactly like the real model.
    x = jnp.asarray(rng.standard_normal((128, 8, 8, cin)), jnp.float32)
    yl = jnp.asarray(rng.integers(0, 1000, (128,)))
    return jax.jit(step, donate_argnums=0).lower(params, x, yl).as_text()


#: Stable pseudo-path for --hlo-step findings, so baseline entries
#: survive across hosts and invocations.
def step_path(kind: str) -> str:
    return f"<hlo-step:{kind}>"
