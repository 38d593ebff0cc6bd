"""Compiled-program ledger — the third half of the telemetry subsystem.

PR 4's metrics/tracing see everything *around* the compiled step (queues,
spans, request latencies); this module sees *inside* it. For every XLA
program compiled on a hot path (MLN/Graph fit in all variants,
ParallelInference, the serving batcher's AOT warmups, bench.py), the
ledger records:

- a stable **program fingerprint** (name + argument shapes/dtypes + a
  hash of the lowered HLO) — recompiles of the same program dedup to one
  entry while ``xla_compiles_total`` keeps counting events;
- **compile wall time** (the AOT ``lower().compile()`` at capture time;
  with the persistent compile cache warm this is the cache-hit cost, and
  the same number is emitted as an ``xla/compile`` trace span);
- ``cost_analysis()`` **FLOPs and bytes accessed** → arithmetic
  intensity and the program's roofline position vs. device peak;
- ``memory_analysis()`` **HBM breakdown** (arguments/output/temps and
  their sum as the peak-residency figure).

On top of the ledger sits a live **MFU accountant**: call sites feed
measured per-step wall time into :func:`observe_step` and the
``train_mfu_pct`` / ``serving_mfu_pct`` gauges report
``flops / step_seconds / device_peak`` — the number ROADMAP item 2 is
chasing, self-reported by every fit and every bench run.

Zero-cost-when-disabled is the same hard contract as ``trace.span()``:
while the ledger is off (default), every hook is one module-global bool
read and the hot paths are byte-identical to the uninstrumented code —
no lowering, no clock reads, no device→host syncs. Backends without
cost/memory analysis degrade gracefully: the probe failure increments
``xla_analysis_unavailable_total{kind=...}`` and the rest of the record
still lands.

Quickstart:

    from deeplearning4j_tpu import monitor
    monitor.xla.enable_ledger("/tmp/perf_ledger.json")
    net.fit(data, epochs=1)                  # programs captured as compiled
    monitor.xla.save_ledger()                # JSON artifact for perf_report
    print(monitor.prometheus_text())         # xla_* families + train_mfu_pct
"""
from __future__ import annotations

import hashlib
import json
import logging
import math
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.monitor import metrics, trace
from deeplearning4j_tpu.util.env import env_float

log = logging.getLogger("deeplearning4j_tpu")

#: THE peak table: per-chip (dense bf16 matmul FLOP/s, HBM bytes/s) by
#: jax device_kind — the MFU denominator and the roofline's memory
#: ceiling (ridge point = flops / bytes). Source: Google Cloud TPU
#: documentation, the "TPU v5e", "TPU v4" and "TPU v6e" system
#: architecture pages. A non-CPU device that is not listed is an error
#: (add it here with its source); CPU has no tabulated peak, and
#: DL4J_TPU_PEAK_FLOPS / DL4J_TPU_HBM_BYTES_PER_SEC stand in for one in
#: smoke tests — the gauge is then only as real as the override.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v6 lite": (918e12, 1640e9),
}

#: compile-time buckets: µs-scale cache hits through multi-minute
#: compiles of a large program.
COMPILE_BUCKETS = (0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0, 180.0, 600.0)

LEDGER_SCHEMA_VERSION = 1

_lock = threading.RLock()
_enabled = False
_default_path: Optional[str] = None
_records: Dict[str, "ProgramRecord"] = {}    # fingerprint -> record
_latest: Dict[str, "ProgramRecord"] = {}     # domain -> last captured/observed
_last_mfu: Dict[str, float] = {}             # domain -> last gauge value
_device_info: Optional[Tuple[str, str]] = None


class ProgramRecord:
    """One distinct compiled XLA program (deduped by fingerprint).

    `flops` / `bytes_accessed` are cost_analysis numbers AS REPORTED by
    XLA, which counts a while/scan body ONCE regardless of trip count —
    so a fused scan-of-K train step reports ~1 step's flops. Callers
    record `steps_per_call` (K for scan/accum programs, 1 otherwise) and
    `total_flops_per_call` is the per-execution figure MFU uses."""

    __slots__ = ("fingerprint", "name", "domain", "arg_shapes", "hlo_hash",
                 "compile_seconds", "compiles", "flops", "bytes_accessed",
                 "hbm", "examples_per_call", "steps_per_call",
                 "first_captured_unix", "arg_shardings", "op_scopes")

    def __init__(self, fingerprint, name, domain, arg_shapes, hlo_hash,
                 compile_seconds, flops, bytes_accessed, hbm,
                 examples_per_call, steps_per_call, arg_shardings=None):
        self.fingerprint = fingerprint
        self.name = name
        self.domain = domain
        self.arg_shapes = arg_shapes
        self.hlo_hash = hlo_hash
        self.compile_seconds = compile_seconds    # first capture's wall time
        self.compiles = 1
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.hbm = hbm                            # dict or None
        self.examples_per_call = examples_per_call
        self.steps_per_call = max(int(steps_per_call), 1)
        #: stringified per-arg PartitionSpecs ("PartitionSpec('data',)",
        #: "replicated", "single", "host") — lets perf_report rooflines
        #: and the MFU accountant tell a GSPMD-plan-sharded program from
        #: a replicated one
        self.arg_shardings = tuple(arg_shardings or ())
        #: compiled instruction name -> the `op_name` XLA kept for it
        #: (the jax.named_scope path of the op, or of a fusion's root):
        #: a device trace names ops by instruction, this maps them back
        #: to the program's scopes ("moe/experts", "opt/update", ...).
        #: As `capture()` fills it, an `OpTable`: a view of the program's
        #: instruction table, `ops`.
        self.op_scopes: Dict[str, str] = {}
        self.first_captured_unix = time.time()

    @property
    def ops(self) -> List[dict]:
        """One row an instruction of the compiled program (fields:
        `parse_hlo_ops`); empty where the program's text was not parsed."""
        return getattr(self.op_scopes, "rows", [])

    @property
    def total_flops_per_call(self) -> Optional[float]:
        if not self.flops:
            return None
        return self.flops * self.steps_per_call

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        if self.flops and self.bytes_accessed:
            return self.flops / self.bytes_accessed
        return None

    @property
    def hbm_peak_bytes(self) -> Optional[int]:
        return hbm_peak(self.hbm)

    @property
    def is_sharded(self) -> bool:
        """True when any argument carries a non-trivial PartitionSpec
        (a mesh axis name appears in it)."""
        return any("PartitionSpec(" in s and s != "PartitionSpec()"
                   for s in self.arg_shardings)

    def to_json(self) -> dict:
        ai = self.arithmetic_intensity
        return {
            "fingerprint": self.fingerprint,
            "name": self.name,
            "domain": self.domain,
            "arg_shapes": list(self.arg_shapes),
            "hlo_hash": self.hlo_hash,
            "compile_seconds": round(self.compile_seconds, 6),
            "compiles": self.compiles,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": None if ai is None else round(ai, 3),
            "hbm": self.hbm,
            "hbm_peak_bytes": self.hbm_peak_bytes,
            "examples_per_call": self.examples_per_call,
            "steps_per_call": self.steps_per_call,
            "total_flops_per_call": self.total_flops_per_call,
            "arg_shardings": list(self.arg_shardings),
            "sharded": self.is_sharded,
            "first_captured_unix": round(self.first_captured_unix, 3),
            # a profile names an instruction; the rows say what it is
            **({"ops": self.ops} if self.ops else {}),
        }

    def brief(self) -> dict:
        """Compact row for bench sweep JSON (full detail in the ledger)."""
        out = {"name": self.name, "fingerprint": self.fingerprint,
               "compile_s": round(self.compile_seconds, 3)}
        total = self.total_flops_per_call
        if total:
            out["gflops_per_call"] = round(total / 1e9, 2)
        ai = self.arithmetic_intensity
        if ai is not None:
            out["arithmetic_intensity"] = round(ai, 2)
        peak = self.hbm_peak_bytes
        if peak:
            out["hbm_peak_bytes"] = peak
        if self.is_sharded:
            out["sharded"] = True
        return out


# ------------------------------------------------------------- lifecycle
def enable_ledger(path: Optional[str] = None):
    """Start capturing compiled programs (idempotent). `path` becomes the
    default for save_ledger(). Registers every xla_* metric family so the
    exposition carries them (with TYPE/HELP) even before the first
    capture — scrapers can alert on absence, not just on values."""
    global _enabled, _default_path
    if path is not None:
        _default_path = path
    _register_families()
    _enabled = True


def disable_ledger():
    global _enabled
    _enabled = False


def ledger_enabled() -> bool:
    return _enabled


#: alias used by the hot-path hooks (reads one module global).
enabled = ledger_enabled


def clear_ledger():
    """Drop every record, the default path, and the cached device lookup
    (tests)."""
    global _device_info, _default_path
    with _lock:
        _records.clear()
        _latest.clear()
        _last_mfu.clear()
        _device_info = None
        _default_path = None


def _register_families():
    metrics.counter("xla_compiles_total",
                    "XLA compile events captured by the program ledger "
                    "(recompiles of the same fingerprint keep counting)",
                    labels=("program",))
    metrics.histogram("xla_compile_seconds",
                      "Compile wall time per captured program (AOT "
                      "lower+compile; cache-hit cost when the persistent "
                      "compile cache is warm)",
                      labels=("program",), buckets=COMPILE_BUCKETS)
    metrics.gauge("xla_programs",
                  "Distinct compiled programs in the ledger (fingerprint-"
                  "deduped)")
    metrics.gauge("xla_program_flops",
                  "cost_analysis() FLOPs per call of the compiled program",
                  labels=("program", "fingerprint"))
    metrics.gauge("xla_program_bytes_accessed",
                  "cost_analysis() bytes accessed per call",
                  labels=("program", "fingerprint"))
    metrics.gauge("xla_program_arithmetic_intensity",
                  "FLOPs / bytes accessed (roofline x-coordinate)",
                  labels=("program", "fingerprint"))
    metrics.gauge("xla_hbm_peak_bytes",
                  "memory_analysis() argument+output+temp bytes of the "
                  "compiled program (peak HBM residency)",
                  labels=("program", "fingerprint"))
    metrics.gauge("xla_program_sharded",
                  "1 when the program's arguments carry non-trivial "
                  "PartitionSpecs (GSPMD plan), 0 when replicated/"
                  "single-device",
                  labels=("program", "fingerprint"))
    metrics.gauge("xla_program_kernel_calls",
                  "Compiled instructions of the program that call one of "
                  "the Pallas kernels of ops/ (an instruction is named "
                  "after its kernel: flash_fwd.133), from the record's "
                  "op_scopes; kernels the program does not call are not "
                  "set",
                  labels=("program", "kernel"))
    metrics.counter("xla_analysis_unavailable_total",
                    "cost/memory analysis probes that degraded (backend "
                    "capability missing, not a lowering bug), by kind",
                    labels=("kind",))
    metrics.gauge("train_mfu_pct",
                  "Live model FLOPs utilization of the training step: "
                  "ledger FLOPs / measured step time / device peak, %")
    metrics.gauge("serving_mfu_pct",
                  "Live model FLOPs utilization of the serving forward, %")


def analysis_unavailable(kind: str):
    """Count a degraded capability probe (shared with util/memory.py's
    backend-without-memory_analysis fallback — counted, never crashing)."""
    metrics.counter("xla_analysis_unavailable_total",
                    "cost/memory analysis probes that degraded (backend "
                    "capability missing, not a lowering bug), by kind",
                    labels=("kind",)).inc(kind=kind)


# --------------------------------------------------------------- devices
def _device() -> Tuple[str, str]:
    global _device_info
    if _device_info is None:
        import jax
        d = jax.devices()[0]
        _device_info = (d.device_kind, d.platform)
    return _device_info


def _tabulated_peaks() -> Optional[Tuple[float, float]]:
    kind, platform = _device()
    if platform == "cpu":
        return None
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no peak FLOP/s / HBM bandwidth tabulated for device_kind "
            f"{kind!r} (platform {platform!r}): add it to "
            "monitor.xla.DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[kind]


def _peak_override(var: str) -> Optional[float]:
    """env_float, but a malformed value DEGRADES to the device table
    with one warning instead of raising: these are telemetry overrides
    read from the MFU accountant on the fit path — a typo'd knob must
    never kill a training run (the fail-loud contract is for knobs read
    at startup)."""
    try:
        return env_float(var)
    except ValueError as e:
        if var not in _warned_overrides:
            _warned_overrides.add(var)
            log.warning("%s — falling back to the device table", e)
        return None


_warned_overrides: set = set()


def device_peak_flops() -> Optional[float]:
    """Peak FLOPs/s for MFU accounting: the env override
    DL4J_TPU_PEAK_FLOPS wins, then DEVICE_PEAKS. None on CPU (the MFU
    gauges are then not set); an unlisted accelerator raises."""
    env = _peak_override("DL4J_TPU_PEAK_FLOPS")
    if env is not None:
        return env
    peaks = _tabulated_peaks()
    return None if peaks is None else peaks[0]


def device_hbm_bytes_per_sec() -> Optional[float]:
    env = _peak_override("DL4J_TPU_HBM_BYTES_PER_SEC")
    if env is not None:
        return env
    peaks = _tabulated_peaks()
    return None if peaks is None else peaks[1]


# --------------------------------------------------------------- capture
def _leaf_sig(leaf) -> str:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return f"{leaf.dtype}[{','.join(map(str, leaf.shape))}]"
    return type(leaf).__name__


def shape_key(tree) -> Tuple[str, ...]:
    """Cheap per-call cache key: shapes/dtypes of every array leaf (no
    device sync, no lowering). Nones disappear with tree flattening."""
    import jax
    return tuple(_leaf_sig(l) for l in jax.tree_util.tree_leaves(tree))


def _leaf_sharding(leaf) -> str:
    """One leaf's placement as a short string: the stringified
    PartitionSpec for mesh-placed jax arrays ("PartitionSpec('data',)"),
    "single" for single-device arrays, "host" for numpy/scalars."""
    s = getattr(leaf, "sharding", None)
    if s is None:
        return "host"
    spec = getattr(s, "spec", None)
    if spec is not None:
        return str(spec)
    return "single"


def sharding_key(tree) -> Tuple[str, ...]:
    """Per-arg placement fingerprint paired with shape_key: the ledger's
    `arg_shardings` field (stringified PartitionSpecs per program), so
    downstream consumers (tools/perf_report.py rooflines, the /metrics
    MFU accountant) can distinguish GSPMD-plan-sharded programs from
    replicated ones."""
    import jax
    return tuple(_leaf_sharding(l) for l in jax.tree_util.tree_leaves(tree))


def hbm_peak(hbm: Optional[Dict[str, int]]) -> Optional[int]:
    """THE peak-residency definition every surface shares (ledger
    records, bench sweep rows, memory_report): arguments + output +
    temps of the compiled program."""
    if not hbm:
        return None
    return (hbm.get("argument_bytes", 0) + hbm.get("output_bytes", 0)
            + hbm.get("temp_bytes", 0))


def hbm_stats(ma) -> Dict[str, int]:
    """CompiledMemoryStats -> plain dict: the one place the attr names
    are spelled (shared with util/memory.py's compiled report)."""
    return {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        "generated_code_bytes": int(
            getattr(ma, "generated_code_size_in_bytes", 0)),
    }


# ------------------------------------------------ the compiled-step table
#: opcodes whose instruction only holds other instructions, whatever it is
#: called (`lax.cond`'s is `cond.N`): its time is its children's
CONTAINER_OPCODES = ("while", "conditional", "call")
#: instructions that do no work on the device: not rows of the table
_NO_WORK = frozenset(("parameter", "get-tuple-element", "tuple", "constant",
                      "bitcast"))
_DTYPE_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
                "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
                "c64": 8, "c128": 16}
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ")
# a TPU shape carries its tiling, `bf16[4,8192]{1,0:T(8,128)(2,1)S(1)}`:
# dtype and dims are all that is read
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,<= ]*)\]")
_TUPLE_END = re.compile(r"\) ([a-z][\w\-]*)\(")
_CALLED = re.compile(r"\b(condition|body|to_apply|calls|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WINDOW_FIELD = re.compile(r"(\w+)=([\w\-]+)")


def _dims(shape: str):
    """[(dtype, [dims])] of the arrays a shape holds (a tuple: each)."""
    return [(t, [int(d.strip("<= ")) for d in dims.split(",") if d.strip()])
            for t, dims in _ARRAY.findall(shape)]


def _shape_bytes(shape: str) -> int:
    return int(sum(
        _DTYPE_BYTES.get(t, 1 if t.startswith("f8") else 0) * math.prod(dims)
        for t, dims in _dims(shape)))


def _dot_flops(attrs: str, result, lhs) -> int:
    """2 x multiply-adds of a `dot`: result elements x contracted extent."""
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
    contracted = m.group(1).split(",") if m and m.group(1) else ()
    return 2 * math.prod(result) * math.prod(lhs[int(d)] for d in contracted)


def _valid_taps(n, k, out, stride, lo, lhs_dilate, rhs_dilate) -> int:
    """(output position, kernel tap) pairs of one spatial dim that meet an
    input element: not padding, not a hole of a dilated input. XLA:TPU
    writes a batched product as a convolution whose window mostly meets
    holes (`size=8 stride=7 lhs_dilate=8`); counting its taps as products
    would count the batch twice."""
    if lo == 0 and lhs_dilate == 1 and (out - 1) * stride \
            + (k - 1) * rhs_dilate < n:
        return out * k
    hit = 0
    for o in range(out):
        for t in range(k):
            at = o * stride - lo + t * rhs_dilate
            if at >= 0 and at % lhs_dilate == 0 and at // lhs_dilate < n:
                hit += 1
    return hit


def _conv_flops(attrs: str, result, lhs, rhs) -> int:
    """2 x multiply-adds of a `convolution` (on a TPU every matrix product
    is one): the result's elements x the kernel's elements / its output
    features where no tap falls on padding or a hole, so grouped features
    count once; where taps do, only those that meet an input element."""
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", attrs)
    if not m:
        return 0
    l_lab, r_lab, o_lab = m.groups()
    window = {}
    w = re.search(r"window=\{([^}]*)\}", attrs)
    for key, val in _WINDOW_FIELD.findall(w.group(1) if w else ""):
        window[key] = val.split("x")
    group = re.search(r"batch_group_count=(\d+)", attrs)
    fma = rhs[r_lab.index("i")] * rhs[r_lab.index("o")] \
        * lhs[l_lab.index("b")] // (int(group.group(1)) if group else 1)
    spatial = [c for c in l_lab if c.isdigit()]
    for i, c in enumerate(spatial):
        field = lambda key, default: window[key][i] if key in window \
            else default
        fma *= _valid_taps(
            lhs[l_lab.index(c)], rhs[r_lab.index(c)], result[o_lab.index(c)],
            int(field("stride", 1)), int(str(field("pad", "0_0")).split(
                "_")[0]), int(field("lhs_dilate", 1)),
            int(field("rhs_dilate", 1)))
    return 2 * fma


def _split_instruction(line: str):
    """(name, shape, opcode, operand names, attributes) of one line of a
    computation's text, or None."""
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    rest = line[m.end():]
    cut = rest.find(", backend_config=")    # a Pallas call's is its body
    if cut >= 0:
        rest = rest[:cut]
    if rest.startswith("("):                # a tuple's shape
        end = _TUPLE_END.search(rest)
        if not end:
            return None
        shape, rest = rest[:end.start() + 1], rest[end.start() + 2:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, rest = rest.partition("(")
    depth, close = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if not depth:
            close = i
            break
    operands = re.findall(r"%([\w.\-]+)", rest[:close]) \
        if opcode != "constant" else []
    return m.group(1), shape, opcode, operands, rest[close + 1:]


def parse_hlo_ops(text: str) -> List[dict]:
    """One row an instruction of a compiled module's text, for every
    computation that executes on the device: the entry, `while` bodies and
    conditions, a conditional's branches, called computations. A fusion's
    body belongs to its fusion; the computations that a reduce, a sort or a
    scatter applies belong to that instruction. Fields of a row:

    - ``name``, ``opcode``, ``kind`` (a fusion's), ``computation``,
      ``parent``: the instruction that calls its computation (None in the
      entry); an instruction whose opcode is in `CONTAINER_OPCODES` only
      holds others;
    - ``scope``: the raw `op_name` (None where XLA kept none); ``layer``,
      ``part``: `monitor.scopes.parse` of it, or, for a fusion that XLA
      left without one, of its body's (the root's, else the first that
      carries one), or, for any other instruction XLA made without one (a
      layout copy, the `copy-done` / `slice-done` of a prefetch), of the
      first op that uses its result and names a part; ``recomputed``:
      the op is the forward made again inside a `jax.checkpoint` region's
      backward (`rematted_computation` in its path); ``direction``: ``"backward"`` under a `transpose(`
      (the op runs in the backward pass: the forward made again too),
      else ``"forward"``; None without a scope;
    - ``dot_flops``: 2 x multiply-adds of every `dot` and `convolution`
      the instruction holds, a fusion those of its body; 0 for custom
      calls (the Pallas kernels, XLA's grouped products), whose work is
      counted from the configuration by their own rooflines;
    - ``bytes_out``, ``bytes_in``: result and operand bytes as the shapes
      say: an UPPER bound where an op reads a slice of an operand
      (`dynamic-slice`, `gather`, the stacked operands of a scan).

    Instructions that do nothing on the device (parameters, tuples and
    their elements, constants, bitcasts) are left out."""
    from deeplearning4j_tpu.monitor import scopes
    comps: Dict[str, list] = {}
    shapes: Dict[str, str] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and line.rstrip().endswith("{"):
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        got = _split_instruction(line)
        if got:
            shapes[got[0]] = got[1]
            current.append(got)

    flops_of: Dict[str, int] = {}

    def body_flops(comp: str) -> int:
        """The dots and convolutions of a fusion's body (a body can hold
        fusions of its own)."""
        if comp not in flops_of:
            flops_of[comp] = 0          # a cycle cannot be; be safe
            flops_of[comp] = sum(own_flops(i) for i in comps.get(comp, ()))
        return flops_of[comp]

    def own_flops(instr) -> int:
        _, shape, opcode, operands, attrs = instr
        if opcode == "fusion":
            called = _CALLED.search(attrs)
            return body_flops(called.group(2)) if called else 0
        if opcode not in ("dot", "convolution") or len(operands) < 2:
            return 0
        dims = lambda s: (_dims(s) or [("", [])])[0][1]
        result = dims(shape)
        lhs, rhs = (dims(shapes.get(o, "")) for o in operands[:2])
        try:
            return _dot_flops(attrs, result, lhs) if opcode == "dot" \
                else _conv_flops(attrs, result, lhs, rhs)
        except (ValueError, IndexError):    # a form this does not know
            return 0

    def own_scope(instr) -> Optional[str]:
        op_name = re.search(r'op_name="([^"]*)"', instr[4])
        return op_name.group(1) if op_name else None

    def body_scope(instr, depth=0) -> Optional[str]:
        """The op_name of a fusion's body: its root's (a fusion's: that
        one's body's), else the first instruction's that carries one."""
        called = _CALLED.search(instr[4])
        body = comps.get(called.group(2), ()) if called else ()
        for inner in (*body[-1:], *body):
            found = own_scope(inner) or (
                body_scope(inner, depth + 1)
                if inner[2] == "fusion" and depth < 8 else None)
            if found:
                return found
        return None

    users: Dict[str, Dict[str, list]] = {}
    placed_by: Dict[str, Optional[str]] = {}

    def user_scope(instr, comp: str, depth=0) -> Optional[str]:
        """For an instruction XLA made without an op_name (a layout copy,
        the `copy-done` / `slice-done` of a prefetch): the op_name of the
        first op that uses its result and says what part it is, through
        other such instructions."""
        if instr[0] in placed_by:
            return placed_by[instr[0]]
        placed_by[instr[0]] = None
        if comp not in users:
            users[comp] = {}
            for other in comps[comp]:
                for operand in other[3]:
                    users[comp].setdefault(operand, []).append(other)
        for user in users[comp].get(instr[0], ()) if depth < 8 else ():
            named = own_scope(user) or (
                body_scope(user) if user[2] == "fusion" else None)
            if named is None:           # XLA's own too: look through it
                named = user_scope(user, comp, depth + 1)
            elif scopes.parse(named)[1] is None:
                continue                # a loop or its tuple: no part
            if named:
                placed_by[instr[0]] = named
                break
        return placed_by[instr[0]]

    rows: List[dict] = []
    seen = set()

    def walk(comp: str, parent: Optional[str]):
        if comp in seen or comp not in comps:
            return
        seen.add(comp)
        for instr in comps[comp]:
            name, shape, opcode, operands, attrs = instr
            if opcode in _NO_WORK:
                continue
            scope = own_scope(instr)
            # a fusion XLA left without an op_name is placed by its
            # body's, any other such instruction by the op it serves
            placed = scope or (body_scope(instr) if opcode == "fusion"
                               else None) or user_scope(instr, comp)
            layer, part = scopes.parse(placed) if placed else (None, None)
            kind = re.search(r"\bkind=(k\w+)", attrs) \
                if opcode == "fusion" else None
            rows.append({
                "name": name, "opcode": opcode,
                "kind": kind.group(1) if kind else None,
                "computation": comp, "parent": parent,
                "scope": scope, "layer": layer, "part": part,
                "recomputed": bool(placed)
                and "rematted_computation" in placed,
                "direction": None if not placed else
                "backward" if "transpose(" in placed else "forward",
                "dot_flops": own_flops(instr),
                "bytes_out": _shape_bytes(shape),
                "bytes_in": sum(_shape_bytes(shapes.get(o, ""))
                                for o in operands)})
            if opcode in CONTAINER_OPCODES:
                called = [c for _, c in _CALLED.findall(attrs)]
                branches = _BRANCHES.search(attrs)
                if branches:
                    called += re.findall(r"%([\w.\-]+)", branches.group(1))
                for c in called:
                    walk(c, name)

    if entry is not None:
        walk(entry, None)
    return rows


class OpTable(dict):
    """``{instruction name: op_name}`` of a compiled program, for the
    instructions that carry one (what `ProgramRecord.op_scopes` has always
    been): a view of ``rows``, the table `parse_hlo_ops` made."""

    def __init__(self, rows=()):
        super().__init__((r["name"], r["scope"]) for r in rows
                         if r["scope"])
        self.rows = list(rows)


def compiled_op_scopes(compiled) -> "OpTable":
    """The one parse of a jax.stages.Compiled: its instruction table
    (`.rows`, see `parse_hlo_ops`) under the map from instruction name to
    `op_name` that the scope readers join a trace with. Empty when the
    backend gives no text."""
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — no HLO text: no map, readers get None
        return OpTable()
    return OpTable(parse_hlo_ops(text or ""))


def compiled_ops(compiled) -> List[dict]:
    """The rows of `compiled_op_scopes(compiled)`: to look at a step
    compiled by hand (chiplessly, say) without the ledger."""
    return compiled_op_scopes(compiled).rows


#: the Pallas kernels of ops/, by the name their `pallas_call` gives the
#: compiled instruction (`flash_fwd.133`, `kda_chunk_bwd.37`)
PALLAS_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                  "kda_chunk_fwd", "kda_chunk_bwd", "dsa_index",
                  "dsa_select", "dsa_attn_fwd", "dsa_kl_fwd", "dsa_attn_bwd",
                  "ssd_chunk_fwd", "ssd_chunk_bwd", "mhc_pre_fwd",
                  "mhc_post_fwd", "mhc_post_bwd", "mhc_pre_bwd")


def kernel_calls(op_scopes: Dict[str, str]) -> Dict[str, int]:
    """{kernel: instructions of a compiled program that call it}, from the
    program's instruction names (the keys of `op_scopes`); a kernel the
    program does not call is left out. What a rematerialised block runs a
    second time shows here: two `flash_fwd` a `flash_bwd_dq` before the
    kernel's results were kept (`ops.REMAT_KEEP`), one since. (The fused
    flash backward keeps the name `flash_bwd_dq`; a `flash_bwd_dkv`
    beside it says the call took the pair of passes.)"""
    calls: Dict[str, int] = {}
    for name in op_scopes:
        kernel = name.split(".", 1)[0]
        if kernel in PALLAS_KERNELS:
            calls[kernel] = calls.get(kernel, 0) + 1
    return calls


def analyze_compiled(compiled):
    """(flops, bytes_accessed, hbm dict) from a jax.stages.Compiled —
    None for whatever the backend cannot answer. The ONE place the XLA
    analysis keys are parsed ('bytes accessed' vs 'bytes_accessed',
    list-wrapped cost dicts, CompiledMemoryStats attrs), shared by
    capture() and bench._bank_analysis so the handling can't drift."""
    flops = bytes_accessed = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            f = float(ca.get("flops", 0.0))
            flops = f if f > 0 else None
            b = float(ca.get("bytes accessed",
                             ca.get("bytes_accessed", 0.0)))
            bytes_accessed = b if b > 0 else None
    # graftlint: disable=bare-except-swallow -- capability probe: capture() counts the degradation via analysis_unavailable('cost') when flops comes back None
    except Exception:
        pass
    hbm = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            hbm = hbm_stats(ma)
    # graftlint: disable=bare-except-swallow -- capability probe: capture() counts the degradation via analysis_unavailable('memory') when hbm comes back None
    except Exception:
        pass
    return flops, bytes_accessed, hbm


def capture(name: str, fn, args, domain: str = "train",
            examples_per_call: Optional[int] = None,
            steps_per_call: int = 1) -> Optional[ProgramRecord]:
    """Capture the compiled program `fn(*args)` into the ledger.

    Call this once per compile EVENT the caller observed (first execution
    of a shape, a post-hot-swap re-jit): every call increments
    ``xla_compiles_total`` and times an AOT ``lower().compile()`` —
    identical fingerprints dedup to one ledger entry. Returns None while
    the ledger is disabled (one bool read) or if lowering itself fails
    (counted, never raised — observability must not take down a fit)."""
    if not _enabled:
        return None
    t0 = time.perf_counter()
    try:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — ledger must never kill a fit
        analysis_unavailable("lower")
        log.warning("xla ledger: capture of %r failed: %r", name, e)
        return None
    t1 = time.perf_counter()
    trace.add_span("xla/compile", t0, t1, program=name, domain=domain)

    try:
        hlo_hash = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:16]
    except Exception:
        hlo_hash = "unavailable"
    arg_shapes = shape_key(args)
    arg_shardings = sharding_key(args)
    fingerprint = hashlib.sha256(
        "|".join((name, hlo_hash) + arg_shapes).encode()).hexdigest()[:16]

    flops, bytes_accessed, hbm = analyze_compiled(compiled)
    if flops is None:
        analysis_unavailable("cost")
    if hbm is None:
        analysis_unavailable("memory")

    with _lock:
        rec = _records.get(fingerprint)
        if rec is None:
            rec = ProgramRecord(fingerprint, name, domain, arg_shapes,
                                hlo_hash, t1 - t0, flops, bytes_accessed,
                                hbm, examples_per_call, steps_per_call,
                                arg_shardings=arg_shardings)
            rec.op_scopes = compiled_op_scopes(compiled)
            _records[fingerprint] = rec
        else:
            rec.compiles += 1
        _latest[domain] = rec
        n_programs = len(_records)

    metrics.counter("xla_compiles_total", labels=("program",)
                    ).inc(program=name)
    metrics.histogram("xla_compile_seconds", labels=("program",),
                      buckets=COMPILE_BUCKETS).observe(t1 - t0, program=name)
    metrics.gauge("xla_programs").set(n_programs)
    if rec.flops:
        metrics.gauge("xla_program_flops",
                      labels=("program", "fingerprint")).set(
            rec.flops, program=name, fingerprint=fingerprint)
    if rec.bytes_accessed:
        metrics.gauge("xla_program_bytes_accessed",
                      labels=("program", "fingerprint")).set(
            rec.bytes_accessed, program=name, fingerprint=fingerprint)
    ai = rec.arithmetic_intensity
    if ai is not None:
        metrics.gauge("xla_program_arithmetic_intensity",
                      labels=("program", "fingerprint")).set(
            ai, program=name, fingerprint=fingerprint)
    peak_bytes = rec.hbm_peak_bytes
    if peak_bytes:
        metrics.gauge("xla_hbm_peak_bytes",
                      labels=("program", "fingerprint")).set(
            peak_bytes, program=name, fingerprint=fingerprint)
    metrics.gauge("xla_program_sharded",
                  labels=("program", "fingerprint")).set(
        1.0 if rec.is_sharded else 0.0, program=name,
        fingerprint=fingerprint)
    for kernel, n in kernel_calls(rec.op_scopes).items():
        metrics.gauge("xla_program_kernel_calls",
                      labels=("program", "kernel")).set(
            n, program=name, kernel=kernel)
    return rec


def capture_cached(cache: dict, key, name: str, fn, args,
                   domain: str = "train",
                   examples_per_call: Optional[int] = None,
                   steps_per_call: int = 1) -> Optional[ProgramRecord]:
    """Hot-loop helper: capture once per caller-observed program (`key`
    is the caller's cheap identity — e.g. (id(jitted_fn), arg shapes)),
    then a dict hit per step. A key can legitimately map to None (capture
    failed) — that negative result is cached too, so a broken lowering
    is probed once, not every step."""
    if not _enabled:
        return None
    if key in cache:
        return cache[key]
    rec = capture(name, fn, args, domain=domain,
                  examples_per_call=examples_per_call,
                  steps_per_call=steps_per_call)
    cache[key] = rec
    return rec


# ----------------------------------------------------------- observation
def observe_step(rec: Optional[ProgramRecord], seconds: float,
                 domain: Optional[str] = None):
    """Feed one measured execution of `rec` (wall seconds) into the MFU
    accountant. train → train_mfu_pct, serving → serving_mfu_pct; the
    gauge is only set when both the program's FLOPs and the device peak
    are known. No-op when the ledger is disabled or rec is None."""
    if rec is None or not _enabled or seconds <= 0:
        return
    d = domain or rec.domain
    with _lock:
        _latest[d] = rec
    peak = device_peak_flops()
    total = rec.total_flops_per_call
    if peak and total:
        mfu = 100.0 * total / seconds / peak
        with _lock:
            _last_mfu[d] = mfu
        metrics.gauge("train_mfu_pct" if d == "train"
                      else "serving_mfu_pct").set(mfu)


def latest_record(domain: str = "train") -> Optional[ProgramRecord]:
    with _lock:
        return _latest.get(domain)


def last_mfu(domain: str = "train") -> Optional[float]:
    with _lock:
        return _last_mfu.get(domain)


def records() -> List[ProgramRecord]:
    with _lock:
        return list(_records.values())


# ------------------------------------------------------------ persistence
def ledger_dict() -> dict:
    """The persisted schema (validated by tools/telemetry_smoke.py and
    consumed by tools/perf_report.py)."""
    kind, backend = _device()
    with _lock:
        progs = [r.to_json() for r in _records.values()]
    return {
        "version": LEDGER_SCHEMA_VERSION,
        "created_unix": round(time.time(), 3),
        "device_kind": kind,
        "backend": backend,
        "peak_flops": device_peak_flops(),
        "hbm_bytes_per_sec": device_hbm_bytes_per_sec(),
        "programs": progs,
    }


def save_ledger(path: Optional[str] = None,
                merge_existing: bool = False) -> int:
    """Atomically write the ledger JSON (tmp + os.replace, like
    save_trace). Returns the number of program records written.

    merge_existing=True folds in the programs an earlier process already
    wrote to `path` (deduped by fingerprint, this process's records win)
    — bench runs every config in its own subprocess against ONE
    DL4J_TPU_PERF_LEDGER file, and a plain overwrite would keep only the
    last config's programs. Configs run sequentially, so read-merge-write
    is race-free there."""
    path = path or _default_path
    if not path:
        raise ValueError("no ledger path: pass one or enable_ledger(path)")
    doc = ledger_dict()
    if merge_existing and os.path.exists(path):
        try:
            with open(path) as f:
                prior = json.load(f)
            ours = {p["fingerprint"] for p in doc["programs"]}
            doc["programs"] = [p for p in prior.get("programs", [])
                               if p.get("fingerprint") not in ours] \
                + doc["programs"]
        except (OSError, ValueError, TypeError, KeyError):
            pass                      # corrupt prior file: overwrite it
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return len(doc["programs"])
