"""Share of its roofline, in percent, that the held experts' grouped
matmuls reach over the traced stage-3 iterations: the least time the chip
could take for the experts' required work over the device seconds of the
ops that implement the grouped matmuls.

Those ops are the grouped-matmul kernels (the Pallas ``gmm`` and
``tgmm``, or XLA's ragged dot) and the ops that only move data into
their operands: a layer's experts sliced out of the stacked weights and
copied into a buffer of their own for the kernel, say.  The kernels'
operands are read from their HLO text on the trace and followed back,
inside the same program, through copies, slices, bitcasts and pads
(:func:`grouped_matmul_seconds`); the device seconds are the union of
those ops' intervals, so an asynchronous copy that overlaps a kernel is
not counted twice.  An implementation that reads the weights where they
lie and one that copies them first are thus read on the same work.

The required work is read from the program's counters over the same
iterations, never from operand shapes, whose rows are padded to the worst
case: ``moe.held_slots.<phase>.<role>``, the slots routed to the experts
held here, and ``moe.active_experts.<phase>.<role>``, the held experts
that got a slot, summed over the layer calls (:func:`expert_work`).
Phases ``gen`` and ``score`` run the forward; ``train`` the forward and
the backward, three times the forward's work (the recomputed forward of
rematerialisation is not counted).  The least time of each phase is the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (:func:`least_by_phase`); their sum over the phases is the
least time of all."""
import bisect
import re

FWD_MATMULS = 3            # gate, up and down projections of SwiGLU
_GROUPED = re.compile(r"%t?gmm(\.\d+)? = |\bragged[-_]dot\b")
_NAME = re.compile(r"^%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")
# where an op's operand list ends in its HLO text
_ATTRS = re.compile(r", (custom_call_target|kind|calls|metadata|"
                    r"backend_config|slice|padding|dimensions)=")
# the HLO ops (alone or fused) that only move data
_MOVES = {"copy", "copy-start", "copy-done", "dynamic-slice", "slice",
          "bitcast", "pad", "reshape", "transpose"}
_TIMES = {"gen": 1, "score": 1, "train": 3}


def expert_work(held_slots, active_experts, d_model, d_ff, itemsize=2):
    """(FLOPs, bytes) of the experts' forward for ``held_slots`` slots
    over layer calls whose experts with a slot number ``active_experts``
    in all: per slot three matmuls of 2 * d_model * d_ff FLOPs; the
    weights of each expert that got a slot read once per call, and each
    slot's rows read and written by each matmul (d_model in and d_ff out
    for gate and up, d_ff in and d_model out for down)."""
    flops = held_slots * FWD_MATMULS * 2 * d_model * d_ff
    weights = active_experts * FWD_MATMULS * d_model * d_ff
    rows = held_slots * FWD_MATMULS * (d_model + d_ff)
    return flops, itemsize * (weights + rows)


def _hlo_name(op):
    m = _NAME.match(op.detail) or _NAME.match(op.name)
    return m.group(1) if m else None


def _operands(op):
    text = op.detail.split(" = ", 1)[-1]
    cut = _ATTRS.search(text)
    return _OPERAND.findall(text[:cut.start()] if cut else text)


def _moves_data(name: str) -> bool:
    """Whether the op ``name`` (``copy-start.3``,
    ``dynamic-slice_bitcast_fusion.14``) is made of data moves alone."""
    kinds = set(re.sub(r"\.\d+$", "", name).split("_")) - {"fusion"}
    return bool(kinds) and kinds <= _MOVES


def grouped_matmul_seconds(device_ops, modules=()) -> float:
    """Device seconds of the grouped-matmul ops and of the data moves
    into their operands: the union of their intervals.  ``modules``
    ((program, start, end), the runs of named programs) keeps an operand
    to the ops of the kernel's own program run, since op names repeat
    across programs."""
    runs = sorted((s, e, n) for n, s, e in modules)
    starts = [r[0] for r in runs]

    def run_of(op):
        i = bisect.bisect_right(starts, op.start) - 1
        return i if i >= 0 and op.start < runs[i][1] else None

    by_run = {}
    for op in device_ops:
        name = _hlo_name(op)
        if name is not None:
            by_run.setdefault(run_of(op), {}).setdefault(name, []).append(op)
    chosen = []
    for ops in by_run.values():
        todo = [o for v in ops.values() for o in v
                if _GROUPED.search(o.detail)]
        seen = {_hlo_name(o) for o in todo}
        chosen += todo
        while todo:
            o = todo.pop()
            for n in _operands(o):
                if n in seen or n not in ops or not _moves_data(n):
                    continue
                seen.add(n)
                chosen += ops[n]
                todo += ops[n]
    busy, end = 0.0, None
    for s, e in sorted((o.start, o.end) for o in chosen):
        if end is None or s > end:
            busy, end = busy + e - s, e
        elif e > end:
            busy, end = busy + e - end, e
    return busy


def least_by_phase(rec) -> dict:
    """{phase: least seconds} of the held experts' work over the traced
    iterations, from the counters."""
    counters = rec["program"]["counters"]
    models, peaks = rec["cell"].config["models"], rec["peaks"]
    out = {}
    for name, held in counters.items():
        m = re.fullmatch(r"moe\.held_slots\.(\w+)\.(\w+)", name)
        if m is None:
            continue
        phase, role = m.groups()
        active = counters.get(f"moe.active_experts.{phase}.{role}", 0)
        sizes = models["actor" if role in ("actor", "ref") else "critic"]
        flops, bytes_ = expert_work(held, active, sizes["d_model"],
                                    sizes["moe_d_ff"])
        k = _TIMES[phase]
        out[phase] = out.get(phase, 0.0) + max(
            k * flops / peaks.bf16_flops, k * bytes_ / peaks.hbm_bytes_per_s)
    return out


def read(rec):
    t, p, peaks = rec.get("trace"), rec.get("program"), rec.get("peaks")
    if t is None or p is None or peaks is None:
        return None
    least = sum(least_by_phase(rec).values())
    busy = grouped_matmul_seconds(t.device_ops, getattr(t, "modules", ()))
    return 100.0 * least / busy if busy > 0 and least > 0 else None
