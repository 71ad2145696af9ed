"""The scope table: which instruction of a compiled program belongs to what.

A device trace names HLO instructions (``%fusion.235``), the program's
spans name host phases; neither says "backward" or "optimizer".  The
compiled step does: every instruction of its optimized module carries the
``op_name`` JAX traced it under, and autodiff leaves its marks there
(``jvp(``, ``transpose(``).  ``scope_table`` reduces ``compiled.as_text()``
to ``{instruction name: tag}`` over the ENTRY computation, with a small
fixed vocabulary:

    fwd            op_name holds ``jvp(`` and no ``transpose(``
    bwd            op_name holds ``transpose(`` (recomputation under
                   remat included: it runs in the backward)
    kernel/<name>  a Pallas custom call, by the kernel's ``name=``
                   (``ops/*.py``), whatever its direction
    update         everything else that carries an op_name: optimizer,
                   loss bookkeeping, casts

An instruction the compiler made without an op_name (a prefetch's
``copy-start``/``copy-done``, a ``ConcatBitcast``) takes the tag of its
first consumer in program order that has one: a weight's prefetch is the
forward's time, not the optimizer's.  Only ``jvp``/``transpose`` decide, so
an executable loaded from a persistent cache that an older build warmed
(metadata is not in the cache's key) reads the same.

Instructions of called computations (a ``while`` body, a conditional's
branches) are not in the table; a reader that joins it with a trace
reports its coverage and gives no number under 95%.

``plan_program`` (``obs/hbm.py``) builds the table from the executable it
already compiles once a run, keeps it in-process, and writes it beside the
host's event file; ``hbm_plan`` events carry the per-tag counts and the
file's name only (``obs hbm`` prints both).  Instruction names repeat from
one program to the next (``fusion.12`` is in the train step and the eval
step alike), so a table goes with its HLO module's name and a trace is
joined module by module.  Readers: the benchmark's
``program_scope.scope_ms`` and ``bench/xprof.op_digest``, in the process
that planned the programs from ``obs/hbm``, in any other (``ddl_tpu bench
digest`` over a stored capture) from the files, ``load_tables``.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

__all__ = [
    "kernel_tiles", "load_tables", "module_name", "opcode_of", "own_name",
    "parts_table", "scope_table", "tag_counts", "write_table",
]

# ``<opcode>(`` after the result type; types hold ``T(8,128)`` and ``S(1)``,
# which no blank precedes
_OPCODE_RX = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_OPERAND_RX = re.compile(r"%([\w.\-]+)")
# the innermost scope around ``pallas_call``: ``jvp(flash_fwd)/pallas_call``
_KERNEL_RX = re.compile(r"([A-Za-z_][\w.\-]*)\)*/pallas_call")
# instructions that never run as a device op of their own
# a kernel's ``metadata`` as XLA prints it: a flat JSON object of strings
_TILES_RX = re.compile(r"kernel_metadata=\{([^{}]*)\}")
_NO_EVENT = frozenset(
    {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
)


def own_name(name: str) -> str:
    """The instruction's own name in a profiler op-event name or an HLO
    line: ``%attn.45 = (...) custom-call(...)`` -> ``attn.45`` (the key
    of a scope table)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def opcode_of(name: str) -> str:
    """The HLO opcode in a profiler op-event name: the first ``word(``
    after the ``=`` that a blank precedes (a tuple type holds blanks of
    its own, so "the word after the type" misreads a kernel); for a bare
    name such as ``fusion.123`` its stem.  A fusion carries its kind
    (``fusion:Loop``).  The same rule as the benchmark's
    ``benchmark/trace.py:opcode_of``."""
    head, sep, rest = name.partition(" = ")
    m = _OPCODE_RX.search(" " + rest) if sep else None
    op = m.group(1) if m else own_name(head).split(".")[0]
    if op == "fusion" and (kind := re.search(r"kind=k(\w+)", name)):
        return f"fusion:{kind.group(1)}"
    return op


def module_name(text: str) -> str:
    """The module's name from the head of its text (``HloModule
    jit_train_step, ...``): what the trace's ``XLA Modules`` events are
    named by, before their ``(<program id>)``."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", text)
    return m.group(1) if m else ""


def _entry_lines(text: str):
    """The ENTRY computation's instructions, one string each, walked in
    place (the text of a large step runs to hundreds of MB: no
    ``splitlines``).  An instruction's line is indented; a kernel's
    ``metadata`` is printed as JSON over several lines that are not, and
    they are joined back onto their instruction."""
    at = text.find("\nENTRY ")
    if at < 0:
        at = 0 if text.startswith("ENTRY ") else -1
    if at < 0:
        return
    pos = text.find("\n", at + 1) + 1
    pending = None
    while 0 < pos < len(text):
        end = text.find("\n", pos)
        if end < 0:
            end = len(text)
        line = text[pos:end]
        if line == "}":
            break
        if line.startswith(" ") or pending is None:
            if pending is not None:
                yield pending
            pending = line
        else:
            pending += line
        pos = end + 1
    if pending is not None:
        yield pending


def _instruction_name(line: str) -> str:
    """``  ROOT %fusion.3 = ...`` -> ``fusion.3``."""
    return line.partition(" = ")[0].split()[-1].lstrip("%")


def _direction(op_name: str) -> str | None:
    if not op_name:
        return None
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "update"


def _op_name(line: str) -> str:
    at = line.find('op_name="')
    if at < 0:
        return ""
    at += len('op_name="')
    return line[at:line.find('"', at)]


def _part_tags(parts: dict[str, str]):
    """``tags_of`` for the second table: the first scope on an
    instruction's ``op_name`` path that ``parts`` names gives its tag,
    ``other`` when none does; a kernel takes the tag of the scope it is
    called in."""
    rx = re.compile(
        "(?:^|/)(" + "|".join(map(re.escape, sorted(parts, key=len, reverse=True)))
        + ")(?:/|$)"
    )

    def tags_of(line: str, name: str, opcode: str) -> tuple[str | None, str | None]:
        op_name = _op_name(line)
        if not op_name:
            return None, None
        m = rx.search(op_name)
        tag = "other" if m is None else parts[m.group(1)]
        return tag, tag

    return tags_of


def _own_tags(line: str, name: str, opcode: str) -> tuple[str | None, str | None]:
    """(the instruction's tag, the tag it hands to operands that have
    none): they differ for a kernel, whose inputs' copies belong to its
    direction and not to the kernel's own time."""
    op_name = _op_name(line)
    direction = _direction(op_name)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in line:
        m = _KERNEL_RX.search(op_name)
        # an unnamed kernel keeps its instruction's stem: unique enough
        # to find, and visibly not one of ours
        return "kernel/" + (m.group(1) if m else name.split(".")[0]), direction
    return direction, direction


def scope_table(text: str) -> dict[str, str]:
    """``{instruction name: tag}`` of the ENTRY computation of an
    optimized HLO module's text; empty when the text has no ENTRY."""
    return _tag_table(text, _own_tags, "update")


def parts_table(text: str, parts: dict[str, str]) -> dict[str, str]:
    """The same instructions tagged by part of the model instead of by
    direction: what share of a step the expert layers, the attention, the
    MLPs and the loss edge take, whatever their direction.  ``parts`` is
    the vocabulary of the caller that owns the scopes, ``{scope name on
    the op_name path: tag}`` (``train/lm_steps.STEP_PARTS``).  Empty when
    no instruction lies in any part."""
    if not parts:
        return {}
    table = _tag_table(text, _part_tags(parts), "other")
    return table if any(tag != "other" for tag in table.values()) else {}


def _tag_table(text: str, tags_of, default: str) -> dict[str, str]:
    rows = []  # (name, own tag, tag handed down, operand names), in program order
    for line in _entry_lines(text):
        _, sep, rest = line.partition(" = ")
        if not sep:
            continue
        name = _instruction_name(line)
        body = " " + rest
        m = _OPCODE_RX.search(body)
        if m is None or m.group(1) in _NO_EVENT:
            continue
        # operands are bare names up to the first ")": the cut keeps a
        # kernel's serialized body out of the regex
        args = body[m.end():body.find(")", m.end())]
        rows.append(
            (name, *tags_of(line, name, m.group(1)), _OPERAND_RX.findall(args))
        )
    table = {name: tag for name, tag, _, _ in rows if tag is not None}
    bare = {name for name, tag, _, _ in rows if tag is None}
    # first consumer in program order wins: walk the users from the last
    # to the first and let each overwrite what a later one handed down
    inherited: dict[str, str] = {}
    for name, _, down, operands in reversed(rows):
        down = down or inherited.get(name)
        if down is None:
            continue
        for op in operands:
            if op in bare:
                inherited[op] = down
    for name in bare:
        table[name] = inherited.get(name, default)
    return table


def tag_counts(table: dict[str, str]) -> dict[str, int]:
    counts: dict[str, int] = {}
    for tag in table.values():
        counts[tag] = counts.get(tag, 0) + 1
    return dict(sorted(counts.items()))


def kernel_tiles(text: str) -> dict[str, dict[str, int]]:
    """What the Pallas kernels of an optimized module's ENTRY say they
    compute, summed by kernel: ``{kernel name: {"calls", "total",
    "computed", "masked", "row_steps"}}`` from each custom call's
    ``kernel_metadata`` (``pallas_call(metadata={"tiles_total": ...})``:
    the flash kernels' sub-tiles in the square, visited, and masked, and
    the (row, K step) pairs of their walk,
    ``ops/flash_attention.flash_tile_plan``; the grouped products' column
    block of a width that 512 does not divide, ``col<width>``, kept as it
    is).  Kernels that carry none,
    and programs without kernels (or interpreted ones), give ``{}``."""
    out: dict[str, dict[str, int]] = {}
    for line in _entry_lines(text):
        if "kernel_metadata=" not in line:
            continue
        m = _TILES_RX.search(line)
        if m is None or 'custom_call_target="tpu_custom_call"' not in line:
            continue
        try:
            meta = json.loads("{" + m.group(1) + "}")
            tiles = {
                k[len("tiles_"):]: int(v)
                for k, v in meta.items() if k.startswith("tiles_")
            }
        except ValueError:
            continue
        if not tiles:
            continue
        tag, _ = _own_tags(line, _instruction_name(line), "custom-call")
        row = out.setdefault(tag.removeprefix("kernel/"), {"calls": 0})
        row["calls"] += 1
        for k, v in tiles.items():
            # a grouped product's column block of a width (``col<width>``)
            # is a size, the same in every call, not a count
            row[k] = v if k.startswith("col") else row.get(k, 0) + v
    return dict(sorted(out.items()))


def write_table(directory, host: int, label: str, module: str, table: dict) -> str:
    """Write one program's table beside the host's event file; returns
    the file's name (the ``hbm_plan`` event carries it)."""
    safe = re.sub(r"[^\w.\-]", "_", str(label))
    name = f"scope-h{int(host):03d}-{safe}.json"
    payload = {
        "label": str(label), "module": module,
        "counts": tag_counts(table), "tags": table,
    }
    with open(Path(directory) / name, "w") as f:
        json.dump(payload, f)
    return name


def load_tables(trace_dir) -> dict[str, dict]:
    """``{HLO module name: table}`` from the ``scope-h*.json`` files of
    the run that ``trace_dir`` belongs to: the nearest directory at or
    above it that holds any (a ``profile_capture`` lies under the job's
    event directory in ``xprof/h<NNN>/<capture>/``, the tables in the
    event directory itself).
    Empty when there is none, or none that reads."""
    here = Path(trace_dir).resolve()
    for directory in (here, *here.parents[:4]):
        tables = {}
        for path in sorted(directory.glob("scope-h*.json")):
            try:
                with open(path) as f:
                    payload = json.load(f)
                tables[payload["module"]] = payload["tags"]
            except (OSError, ValueError, KeyError):
                continue
        if tables:
            return tables
    return {}
