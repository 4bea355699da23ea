"""Plain reference, part 1: from loop-body source text to a compiled
program, for one machine model given as data.

This is the benchmark's own copy of the predictor's front half (parse,
innermost-loop extraction, instruction-form lookup, dependency edges,
uop compilation, front-end schedule).  It imports nothing of the
program under test and reads the machine model from the configuration
file, so a later change to the program cannot move the yardstick.  Only
AT&T syntax is parsed: the benchmark's generator emits nothing else.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

# --------------------------------------------------------------------------
# Parsing
# --------------------------------------------------------------------------

_GPR64 = {"rax", "rbx", "rcx", "rdx", "rsi", "rdi", "rbp", "rsp",
          *(f"r{i}" for i in range(8, 16))}
_GPR32 = {"eax", "ebx", "ecx", "edx", "esi", "edi", "ebp", "esp",
          *(f"r{i}d" for i in range(8, 16))}
_GPR16 = {"ax", "bx", "cx", "dx", "si", "di", "bp", "sp",
          *(f"r{i}w" for i in range(8, 16))}
_GPR8 = {"al", "bl", "cl", "dl", "ah", "bh", "ch", "dh", "sil", "dil",
         "bpl", "spl", *(f"r{i}b" for i in range(8, 16))}

BRANCHES = {
    "jmp", "ja", "jae", "jb", "jbe", "jc", "je", "jg", "jge", "jl", "jle",
    "jna", "jnae", "jnb", "jnbe", "jnc", "jne", "jng", "jnge", "jnl",
    "jnle", "jno", "jnp", "jns", "jnz", "jo", "jp", "js", "jz", "loop",
}
_SUFFIXABLE = {
    "add", "sub", "cmp", "test", "mov", "inc", "dec", "and", "or", "xor",
    "neg", "not", "shl", "shr", "sar", "sal", "lea", "imul", "mul", "push",
    "pop", "adc", "sbb", "bt", "movz", "movs",
}


def register_class(name: str) -> str:
    n = name.lower()
    for prefix in ("zmm", "ymm", "xmm"):
        if n.startswith(prefix):
            return prefix
    if n.startswith("k") and n[1:].isdigit():
        return "k"
    for token, names in (("r64", _GPR64), ("r32", _GPR32),
                         ("r16", _GPR16), ("r8", _GPR8)):
        if n in names:
            return token
    if n in ("rip", "eip"):
        return "rip"
    if n.startswith("st"):
        return "st"
    return "reg"


@dataclass(frozen=True)
class Operand:
    kind: str                 # "reg" | "mem" | "imm" | "label"
    text: str
    reg: str | None = None
    base: str | None = None
    index: str | None = None
    scale: int = 1
    displacement: int = 0

    @property
    def type_token(self) -> str:
        if self.kind == "reg":
            return register_class(self.reg or "")
        return self.kind


@dataclass(frozen=True)
class Instruction:
    mnemonic: str
    operands: tuple[Operand, ...]     # Intel order: destination first
    label: str | None = None

    @property
    def signature(self) -> tuple[str, ...]:
        return tuple(op.type_token for op in self.operands)


def _normalise_mnemonic(raw: str) -> str:
    m = raw.lower()
    if m in BRANCHES:
        return m
    if m.startswith(("movz", "movs")) and len(m) <= 6 and not m.startswith(
            ("movss", "movsd", "movsh")):
        return m[:4]
    if m and m[-1] in "bwlq" and m[:-1] in _SUFFIXABLE:
        return m[:-1]
    return m


_LABEL_RE = re.compile(r"^\s*([.\w$@]+):")
_MEM_RE = re.compile(
    r"^\s*(?P<disp>[-+]?(?:0x[0-9a-fA-F]+|\d+))?\s*"
    r"\(\s*(?:%(?P<base>\w+))?\s*(?:,\s*%(?P<index>\w+)\s*"
    r"(?:,\s*(?P<scale>[1248]))?)?\s*\)\s*$")
_INT_RE = re.compile(r"^[-+]?(0x[0-9a-fA-F]+|\d+)$")


def _parse_int(s: str) -> int:
    s = s.strip()
    neg = s.startswith("-")
    s = s.lstrip("+-")
    val = int(s, 16) if s.lower().startswith("0x") else int(s)
    return -val if neg else val


def _split_operands(text: str) -> list[str]:
    out, depth, cur = [], 0, []
    for ch in text:
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return out


def _operand(text: str) -> Operand:
    t = text.strip()
    if t.startswith("$"):
        return Operand("imm", t)
    if t.startswith("%"):
        return Operand("reg", t, reg=t[1:].rstrip(")"))
    if t.startswith("*"):
        return Operand("mem", t)
    m = _MEM_RE.match(t)
    if m:
        return Operand("mem", t, base=m.group("base"),
                       index=m.group("index"),
                       scale=int(m.group("scale") or 1),
                       displacement=_parse_int(m.group("disp"))
                       if m.group("disp") else 0)
    if _INT_RE.match(t):
        return Operand("mem", t, displacement=_parse_int(t))
    return Operand("label", t)


def parse(source: str) -> list[Instruction]:
    """AT&T listing -> instructions (operands in Intel order)."""
    out: list[Instruction] = []
    label: str | None = None
    for raw in source.splitlines():
        line = raw.split("#")[0].split(";")[0].strip()
        if not line:
            continue
        lm = _LABEL_RE.match(line)
        if lm:
            label = lm.group(1)
            line = line[lm.end():].strip()
            if not line:
                continue
        if line.startswith((".", "#")):
            continue
        parts = line.split(None, 1)
        mnemonic = parts[0].lower()
        if mnemonic in ("lock", "rep", "repz", "repnz", "data16"):
            parts = parts[1].split(None, 1)
            mnemonic = parts[0].lower()
        tokens = _split_operands(parts[1]) if len(parts) > 1 else []
        ops = [_operand(t) for t in tokens][::-1]
        out.append(Instruction(_normalise_mnemonic(mnemonic), tuple(ops),
                               label))
        label = None
    return out


def loop_body(source: str) -> list[Instruction]:
    """The innermost loop: the shortest span from a label to a backward
    branch to it (the whole listing where there is none)."""
    instrs = parse(source)
    label_pos: dict[str, int] = {}
    for idx, ins in enumerate(instrs):
        if ins.label:
            label_pos.setdefault(ins.label, idx)
    best: tuple[int, int] | None = None
    for idx, ins in enumerate(instrs):
        if ins.mnemonic not in BRANCHES or not ins.operands:
            continue
        tpos = label_pos.get(ins.operands[0].text.strip())
        if tpos is None or tpos > idx:
            continue
        if best is None or idx - tpos < best[1] - best[0]:
            best = (tpos, idx)
    return instrs if best is None else instrs[best[0]:best[1] + 1]


# --------------------------------------------------------------------------
# The machine model, read from the configuration file
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Uop:
    ports: tuple[str, ...]
    cycles: float = 1.0
    hideable_load: bool = False
    kind: str = ""


@dataclass(frozen=True)
class Form:
    mnemonic: str
    signature: tuple[str, ...]
    uops: tuple[Uop, ...]
    latency: float


@dataclass(frozen=True)
class Pipeline:
    issue_width: int
    rob_size: int
    scheduler_size: int
    retire_width: int
    predecode_width: int = 0
    decode_width: int = 0
    complex_decode_width: int = 1
    dsb_width: int = 0
    dsb_size: int = 0
    lsd_size: int = 0
    macro_fusion: bool = False
    micro_fusion: bool = False
    move_elimination: bool = False
    mispredict_penalty: float = 0.0


class Machine:
    """Ports, pipeline and instruction-form table of one machine model
    (the ``machine`` object of a configuration file)."""

    def __init__(self, spec: dict):
        self.ports: tuple[str, ...] = tuple(spec["ports"])
        self.store_hides_load = bool(spec.get("store_hides_load", False))
        self.store_forward_latency = float(
            spec.get("store_forward_latency", 0.0))
        pl = spec["pipeline"]
        bools = ("macro_fusion", "micro_fusion", "move_elimination")
        self.pipeline = Pipeline(**{
            k: bool(v) if k in bools else float(v)
            if k == "mispredict_penalty" else int(v)
            for k, v in pl.items()})
        self._exact: dict[tuple, Form] = {}
        self._default: dict[str, Form] = {}
        self.forms: list[Form] = []
        for f in spec["forms"]:
            form = Form(f["mnemonic"], tuple(f["signature"]), tuple(
                Uop(tuple(u["ports"]), float(u.get("cycles", 1.0)),
                    bool(u.get("hideable_load", False)),
                    str(u.get("kind", ""))) for u in f["uops"]),
                float(f["latency"]))
            self.forms.append(form)
            if form.signature == ("*",):
                self._default[form.mnemonic] = form
            else:
                self._exact[(form.mnemonic, form.signature)] = form

    def lookup(self, ins: Instruction) -> Form | None:
        """Exact form, then GPR widths collapsed to ``r``, then
        immediates read as registers, then the mnemonic's default."""
        sig = ins.signature
        collapsed = tuple("r" if t in ("r8", "r16", "r32", "r64", "reg")
                          else t for t in sig)
        relaxed = tuple("r" if t == "imm" else t for t in collapsed)
        for key in ((ins.mnemonic, sig), (ins.mnemonic, collapsed),
                    (ins.mnemonic, relaxed)):
            hit = self._exact.get(key)
            if hit is not None:
                return hit
        return self._default.get(ins.mnemonic)


# --------------------------------------------------------------------------
# Dependency edges
# --------------------------------------------------------------------------

_RMW = {"add", "sub", "inc", "dec", "and", "or", "xor", "neg", "not",
        "shl", "shr", "sar", "adc", "sbb", "imul"}
_ZERO_IDIOMS = {"xor", "vxorpd", "vxorps", "vpxor", "pxor", "xorps",
                "xorpd", "sub"}
_ALIAS_64 = {"eax": "rax", "ebx": "rbx", "ecx": "rcx", "edx": "rdx",
             "esi": "rsi", "edi": "rdi", "ebp": "rbp", "esp": "rsp"}


def _canon_reg(name: str) -> str:
    n = name.lower()
    if n in _ALIAS_64:
        return _ALIAS_64[n]
    if n.endswith("d") and n[:-1].startswith("r") and n[1:-1].isdigit():
        return n[:-1]
    return n


def _key(op: Operand) -> str | None:
    if op.kind == "reg":
        return f"reg:{_canon_reg(op.reg or '')}"
    if op.kind == "mem":
        return f"mem:{op.base}+{op.index}*{op.scale}+{op.displacement}"
    return None


def _reads_writes(ins: Instruction) -> tuple[list[str], list[str]]:
    ops = ins.operands
    if not ops:
        return [], []
    regs = [op.reg for op in ops if op.kind == "reg"]
    if ins.mnemonic in _ZERO_IDIOMS and len(regs) == len(ops) \
            and len(set(regs)) == 1:
        return [], [f"reg:{_canon_reg(ops[0].reg or '')}"]
    reads: list[str] = []
    writes: list[str] = []
    dk = _key(ops[0])
    if dk is not None:
        writes.append(dk)
        if ins.mnemonic in _RMW and ops[0].kind != "mem":
            reads.append(dk)
    for op in ops:
        if op.kind == "mem":
            reads += [f"reg:{_canon_reg(r)}" for r in (op.base, op.index)
                      if r]
    reads += [k for k in map(_key, ops[1:]) if k is not None]
    if ins.mnemonic in ("cmp", "test"):
        writes.clear()
        if dk:
            reads.append(dk)
    return reads, writes


def dependency_edges(kernel: list[Instruction], machine: Machine
                     ) -> list[tuple[int, int, float, bool]]:
    """``(src, dst, weight, wrap)`` over two unrolled iterations; wrap
    edges are loop-carried.  Forwarded memory reads weigh the
    store-to-load forwarding latency."""
    lat = []
    for ins in kernel:
        e = machine.lookup(ins)
        lat.append(e.latency if e is not None else 1.0)
    rw = [_reads_writes(ins) for ins in kernel]
    stores = [bool(ins.operands) and ins.operands[0].kind == "mem"
              for ins in kernel]
    edges: list[tuple[int, int, float, bool]] = []
    writer: dict[str, tuple[int, int]] = {}
    for it in range(2):
        for i in range(len(kernel)):
            reads, writes = rw[i]
            for k in reads:
                w = writer.get(k)
                if w is None:
                    continue
                wit, widx = w
                weight = lat[widx]
                if k.startswith("mem:") and stores[widx]:
                    weight = machine.store_forward_latency or lat[widx]
                if wit != it:
                    edges.append((widx, i, weight, True))
                elif widx < i:
                    edges.append((widx, i, weight, False))
            for k in writes:
                writer[k] = (it, i)
    return edges


# --------------------------------------------------------------------------
# Compilation to uops
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class SimUop:
    instr_index: int
    ports: tuple[str, ...]
    cycles: float = 1.0


@dataclass(frozen=True)
class Program:
    n_instructions: int
    uops: tuple[SimUop, ...]
    latency: tuple[float, ...]
    edges: tuple[tuple[int, int, float, bool], ...]
    fuse_prev: tuple[bool, ...]
    eliminable: tuple[bool, ...]
    macro_prev: tuple[bool, ...]
    port_bound: float


_MEMORY_KINDS = ("load", "store-agu", "store-data", "div")


def _laminates(prev_kind: str, kind: str) -> bool:
    compute_prev = prev_kind not in _MEMORY_KINDS
    if kind == "load":
        return compute_prev
    if prev_kind == "load":
        return kind not in _MEMORY_KINDS
    if kind == "div":
        return compute_prev
    if prev_kind == "store-agu":
        return kind in ("store-agu", "store-data")
    return False


def _eliminable(ins: Instruction) -> bool:
    m = ins.mnemonic
    if not (m == "mov" or m.startswith("vmov") or m in (
            "movapd", "movaps", "movupd", "movups", "movsd", "movss",
            "movdqa", "movdqu")):
        return False
    return len(ins.operands) == 2 and all(o.kind == "reg"
                                          for o in ins.operands)


def compile_body(kernel: list[Instruction], machine: Machine) -> Program:
    """Match forms and flatten one loop body into uops.  Unmatched
    instructions give no uops and keep a 1-cycle latency; on a
    store-hides-load machine each store lets the first remaining
    hideable load run port-less in its shadow."""
    entries = [machine.lookup(ins) for ins in kernel]
    hidden: set[int] = set()
    if machine.store_hides_load:
        budget = sum(1 for e in entries if e is not None
                     and any(u.kind == "store-agu" for u in e.uops))
        for idx, e in enumerate(entries):
            if budget == 0:
                break
            if e is not None and any(u.hideable_load for u in e.uops):
                hidden.add(idx)
                budget -= 1
    uops: list[SimUop] = []
    fuse_prev: list[bool] = []
    eliminable: list[bool] = []
    lat: list[float] = []
    for idx, e in enumerate(entries):
        lat.append(e.latency if e is not None else 1.0)
        if e is None:
            continue
        elim = _eliminable(kernel[idx])
        prev_kind: str | None = None
        prev_fused = False
        for u in e.uops:
            fused = (prev_kind is not None and not prev_fused
                     and _laminates(prev_kind, u.kind))
            ports = () if idx in hidden and u.hideable_load else u.ports
            uops.append(SimUop(idx, tuple(ports), max(1.0, u.cycles)))
            fuse_prev.append(fused)
            eliminable.append(elim)
            prev_kind, prev_fused = u.kind, fused
    macro_prev = tuple(
        idx > 0 and kernel[idx].mnemonic in BRANCHES
        and kernel[idx - 1].mnemonic in ("cmp", "test")
        for idx in range(len(kernel)))
    occ = {p: 0.0 for p in machine.ports}
    for u in uops:
        for p in u.ports:
            occ[p] += u.cycles / len(u.ports)
    return Program(len(kernel), tuple(uops), tuple(lat),
                   tuple(dependency_edges(kernel, machine)),
                   tuple(fuse_prev), tuple(eliminable), macro_prev,
                   max(occ.values(), default=0.0))


def compile_source(source: str, machine: Machine) -> Program:
    return compile_body(loop_body(source), machine)


def fits_windows(prog: Program, pipeline: Pipeline) -> bool:
    """One iteration's uops fit the ROB and scheduler windows and each
    instruction's uops are contiguous: the bodies the compiled device
    recurrence takes (longer ones take a host path)."""
    seen: set[int] = set()
    prev = -1
    for u in prog.uops:
        if u.instr_index != prev and u.instr_index in seen:
            return False
        seen.add(u.instr_index)
        prev = u.instr_index
    n_port = sum(1 for u in prog.uops if u.ports)
    return (len(prog.uops) <= pipeline.rob_size
            and n_port <= pipeline.scheduler_size)


# --------------------------------------------------------------------------
# Front end: fusion slots and the static delivery schedule
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Frontend:
    slot_of: tuple[int, ...]
    slot_start: tuple[bool, ...]
    n_slots: int
    eliminated: tuple[bool, ...]
    mode: str
    phase: tuple[float, ...]
    cpi: float


def frontend(prog: Program, p: Pipeline) -> Frontend:
    n = len(prog.uops)
    eliminated = tuple(p.move_elimination and e for e in prog.eliminable)
    slot_of: list[int] = []
    s = -1
    for i in range(n):
        if not (p.micro_fusion and prog.fuse_prev[i] and s >= 0):
            s += 1
        slot_of.append(s)
    n_slots = s + 1
    slot_start = tuple(i == 0 or slot_of[i] != slot_of[i - 1]
                       for i in range(n))
    mode, phase, cpi = "ideal", (0.0,) * n_slots, 0.0
    if n_slots:
        if p.lsd_size and n_slots <= p.lsd_size:
            mode = "lsd"
        elif p.dsb_width and p.dsb_size and n_slots <= p.dsb_size:
            mode = "dsb"
            phase = tuple(float(i // p.dsb_width) for i in range(n_slots))
            cpi = float(-(-n_slots // p.dsb_width))
        elif p.decode_width:
            mode = "mite"
            phase, cpi = _decode_walk(prog, p, slot_of, slot_start,
                                      n_slots)
    return Frontend(tuple(slot_of), slot_start, n_slots, eliminated, mode,
                    phase, cpi)


def _decode_walk(prog: Program, p: Pipeline, slot_of: list[int],
                 slot_start: tuple[bool, ...], n_slots: int
                 ) -> tuple[tuple[float, ...], float]:
    slots_of_instr: list[list[int]] = [[] for _ in
                                       range(prog.n_instructions)]
    for i, u in enumerate(prog.uops):
        if slot_start[i]:
            slots_of_instr[u.instr_index].append(slot_of[i])
    units: list[tuple[int, list[int]]] = []
    for idx in range(prog.n_instructions):
        if p.macro_fusion and prog.macro_prev[idx] and units:
            raw, slots = units[-1]
            units[-1] = (raw + 1, slots + slots_of_instr[idx])
        else:
            units.append((1, list(slots_of_instr[idx])))
    pw = p.predecode_width
    cw = max(1, p.complex_decode_width)
    phase = [0.0] * n_slots
    raw_done = cyc = used = complex_used = 0
    for raw, slots in units:
        pre = (raw_done + raw - 1) // pw if pw else 0
        raw_done += raw
        is_complex = len(slots) > 1
        while True:
            if cyc < pre:
                cyc, used, complex_used = pre, 0, 0
            if used >= p.decode_width or (is_complex
                                          and complex_used >= cw):
                cyc, used, complex_used = cyc + 1, 0, 0
                continue
            break
        used += 1
        complex_used += is_complex
        for s in slots:
            phase[s] = float(cyc)
    return tuple(phase), float(cyc + 1)


def classify(cpi: float, frontend_cycles: float, port_bound: float,
             delivery: float, fe_mode: str) -> str:
    """The binding constraint of a steady state."""
    if cpi <= 0:
        return "empty"
    if cpi <= max(frontend_cycles, delivery) * 1.02 + 0.51:
        if delivery > frontend_cycles * 1.02:
            return "decode" if fe_mode == "mite" else "dsb"
        return "frontend"
    if cpi <= port_bound * 1.05 + 0.51:
        return "ports"
    return "dependencies"
