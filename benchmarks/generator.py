"""The one general traffic generator.

A traffic mix is a data file ``benchmarks/traffic/<mix>.json``; this
module turns it and ``--seed`` into the statements of a run.  It knows
no statement, suite or scale: a later PR adds a mix by adding a file.

The file::

    {"loop": "closed", "clients": 1,        # passes back to back
     "trace_slice_s": 10,                   # traced slice: whole passes
     "statements": [
       {"name": "q6",                       # unique within the mix
        "template": "nds_h/q6",             # traffic/sql/<template>.txt
                                            # and reference/<template>.py
        "sets": [{...}, ...],               # the parameter sets, written
                                            # out; one set: the spec's
                                            # qualification values
        "order_by": [[column, "asc"|"desc"], ...],
                                            # the statement's ORDER BY as
                                            # positions in its SELECT list
        "need": [[table, rows, bytes_per_row], ...],
                                            # optional, for the roofline
        "writes": [table, ...]}]}           # optional: a WRITE, whose
                                            # template is several
                                            # ';'-separated statements
                                            # (a refresh function) that
                                            # mutate these tables

A pass runs the mix's writes first, in the file's order (a refresh
run's order), and then its reads.  What ``--seed`` makes: the order of
the reads within a pass (one permutation per run) and the order in
which a statement's parameter sets come round (pass *i* uses the run's
*i* mod len(sets)-th).  Every
seed gives the same statements and the same work in another order: a
seed that drew its own parameters changed the device time of a pass by
up to 6 % (PERF.md section 6), which no bound could tell from a
regression.
"""

from __future__ import annotations

import json
import os
import random
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Statement:
    name: str          # the traffic file's name of the statement
    template: str      # "<suite>/<file stem>"
    variant: int       # which of the statement's parameter sets
    params: dict
    sql: str
    order_by: tuple    # ((column position, "asc" | "desc"), ...)
    need_bytes: int    # bytes the statement's SQL has to read; 0: not given
    writes: tuple = ()  # the tables a write mutates; () for a read

    @property
    def label(self) -> str:
        return f"{self.name}#{self.variant}"

    @property
    def parts(self) -> list:
        """The statements ``session.sql`` runs one by one: a read is
        one; a write's comment lines go and it splits at ';'."""
        if not self.writes:
            return [self.sql]
        body = "\n".join(ln for ln in self.sql.splitlines()
                         if not ln.lstrip().startswith("--"))
        return [p.strip() for p in body.split(";") if p.strip()]


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError(f"{path}: this generator drives closed loops of "
                         f"one client; got loop={mix.get('loop')!r} "
                         f"clients={mix.get('clients')!r}")
    names = [s["name"] for s in mix["statements"]]
    if len(set(names)) != len(names):
        raise ValueError(f"{path}: statement names repeat")
    for s in mix["statements"]:
        if "writes" in s and not (isinstance(s["writes"], list)
                                  and s["writes"]):
            raise ValueError(f"{path}: {s['name']}: 'writes' names the "
                             f"tables the statement mutates")
    return mix


def render(template: str, params: dict) -> str:
    with open(os.path.join(HERE, "traffic", "sql", f"{template}.txt")) as f:
        text = f.read()
    return text.format(**params).strip().rstrip(";").strip()


def variants(mix: dict, seed: int) -> "dict[str, list[Statement]]":
    """name -> that statement's parameter sets, rendered, in this run's
    order."""
    out = {}
    for entry in mix["statements"]:
        order_by = tuple((int(c), d) for c, d in entry.get("order_by", []))
        need = sum(int(rows) * int(width)
                   for _t, rows, width in entry.get("need", []))
        writes = tuple(entry.get("writes", ()))
        pool = [Statement(entry["name"], entry["template"], i, dict(p),
                          render(entry["template"], p), order_by, need,
                          writes)
                for i, p in enumerate(entry["sets"])]
        random.Random(f"{seed}:{entry['name']}").shuffle(pool)
        out[entry["name"]] = pool
    return out


def order(mix: dict, seed: int) -> list:
    """The names of a pass: the writes in the file's order, then the
    reads in this run's order."""
    writes = [s["name"] for s in mix["statements"] if s.get("writes")]
    reads = [s["name"] for s in mix["statements"] if not s.get("writes")]
    random.Random(f"{seed}:order").shuffle(reads)
    return writes + reads


def pass_statements(sets: dict, names: list, i: int) -> list:
    """The statements of pass ``i``, in the run's order."""
    return [sets[n][i % len(sets[n])] for n in names]


def distinct(mix: dict, sets: dict) -> list:
    """Every distinct statement of the run, in the traffic file's order
    and not the run's, the writes first as in a pass: set-up (warm-up,
    uploads, device allocations) then does the same thing in the same
    order whatever the seed."""
    seen, out = set(), []
    entries = mix["statements"]
    for entry in ([e for e in entries if e.get("writes")]
                  + [e for e in entries if not e.get("writes")]):
        for s in sorted(sets[entry["name"]], key=lambda s: s.variant):
            if s.sql not in seen:
                seen.add(s.sql)
                out.append(s)
    return out
