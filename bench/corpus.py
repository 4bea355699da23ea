"""Loop bodies drawn from a seed: the inputs of every cell.

Each body is a BHive-style basic block turned into a loop: instruction
forms drawn from the configuration's own form table, spelled in AT&T
syntax with register dependency chains (a source reads one of the last
three written registers with probability ``reuse``), closed by the
3-instruction loop control.  Body ``i`` of a pool depends only on
``(seed, config, i)``, so a cell generates just the bodies its window
uses.  Lengths come from a fixed table of quantiles of a log-normal
(``length`` in the configuration), so every seed draws the same multiset
of lengths, only in another order.  Bodies whose uops do not fit the ROB
and scheduler windows are redrawn: the compiled recurrence takes only
bodies that fit.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist

from .reference.x86 import (BRANCHES, Machine, Program, compile_source,
                            fits_windows, loop_body)

# registers the generator spells with; %rax (induction), %rbp (trip
# count) and the memory bases stay out so the loop control is intact
_GPR64 = ("rbx", "rcx", "rdx", "r8", "r9", "r10", "r11", "r12", "r13",
          "r14", "r15")
_GPR32 = ("ebx", "ecx", "edx", "r8d", "r9d", "r10d", "r11d", "r12d",
          "r13d", "r14d", "r15d")
_BASES = ("rsi", "rdi")
_TOKENS = {"xmm", "ymm", "mem", "imm", "r", "r64", "r32"}
_LOOP_CONTROL = ("addq $8, %rax", "cmpq %rbp, %rax")


def spell(form, rng: random.Random, vec_live: list, gpr_live: list,
          reuse: float) -> str:
    """One instruction of ``form`` in AT&T syntax."""
    ops = []
    for pos, tok in enumerate(form.signature):      # Intel order
        src = pos > 0
        if tok in ("xmm", "ymm"):
            if src and vec_live and rng.random() < reuse:
                n = rng.choice(vec_live[-3:])
            else:
                n = rng.randrange(16)
            if not src:
                vec_live.append(n)
            ops.append(f"%{tok}{n}")
        elif tok in ("r", "r64", "r32"):
            names = _GPR32 if tok == "r32" else _GPR64
            if src and gpr_live and rng.random() < reuse:
                i = rng.choice(gpr_live[-3:])
            else:
                i = rng.randrange(len(names))
            if not src:
                gpr_live.append(i)
            ops.append(f"%{names[i]}")
        elif tok == "mem":
            ops.append(f"{8 * rng.randrange(8)}(%{rng.choice(_BASES)},"
                       f"%rax,8)")
        else:
            ops.append(f"${rng.randrange(1, 64)}")
    return f"{form.mnemonic} {', '.join(reversed(ops))}".strip()


def usable_forms(machine: Machine) -> list:
    """The forms the generator can spell and that parse back to a form
    of the same table (branches and stack operations left out)."""
    rng = random.Random(0)
    forms = []
    for form in machine.forms:
        sig = form.signature
        if not sig or not set(sig) <= _TOKENS or form.mnemonic in BRANCHES \
                or form.mnemonic in ("push", "pop", "call", "ret"):
            continue
        body = loop_body(f".L0:\n{spell(form, rng, [], [], 0.0)}\n"
                         "jne .L0\n")
        if len(body) == 2 and machine.lookup(body[0]) is not None:
            forms.append(form)
    return forms


def length_table(spec: dict, n: int) -> list[int]:
    """``n`` body lengths (instruction forms, loop control excluded):
    the ``n`` mid-quantiles of a log-normal with the given median and
    sigma, rounded and clipped to ``[min, max]``."""
    dist = NormalDist(math.log(spec["median"]), spec["sigma"])
    return [min(spec["max"], max(spec["min"], round(math.exp(
        dist.inv_cdf((k + 0.5) / n))))) for k in range(n)]


class Corpus:
    """Lazily generated bodies of one configuration and seed."""

    def __init__(self, config: dict, machine: Machine, seed: int,
                 pool: str):
        self.config = config
        self.machine = machine
        self.seed = seed
        self.pool = pool
        self.forms = usable_forms(machine)
        self.reuse = config["corpus"]["reuse"]

    def body(self, index: int, forms: tuple[int, int]
             ) -> tuple[str, Program]:
        """Body ``index`` with between ``forms[0]`` and ``forms[1]``
        instruction forms, redrawn until it fits the windows."""
        rng = random.Random(f"{self.config['name']}/{self.pool}/"
                            f"{self.seed}/{index}")
        label = f".L{index}"
        for _ in range(10_000):
            vec: list = []
            gpr: list = []
            lines = [f"{label}:"] + [
                spell(rng.choice(self.forms), rng, vec, gpr, self.reuse)
                for _ in range(rng.randint(*forms))]
            src = "\n".join(lines + list(_LOOP_CONTROL)
                            + [f"jne {label}"]) + "\n"
            prog = compile_source(src, self.machine)
            if fits_windows(prog, self.machine.pipeline):
                return src, prog
        raise RuntimeError(f"no body of {forms} forms fits: index "
                           f"{index}")
