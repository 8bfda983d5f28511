"""ndslint rules: the repo's recurring hazard classes as ast checks.

Every rule here encodes a bug class an advisor round actually found by
hand (ADVICE.md rounds 1-5) — the linter exists so the NEXT instance
fails CI instead of waiting for a human audit:

- NDS101 id-keyed-cache     storing under ``id(obj)`` without the value
                            pinning the object: a recycled address
                            serves another object's cached state
                            (round-5 `_stage_plans` finding).
- NDS102 raw-timing         ``time.time()/perf_counter()/monotonic()``
                            inside ``engine/`` / ``parallel/``: timing
                            bills belong to ``obs`` spans so traces and
                            CSVs can never drift apart.
- NDS103 unsynced-timing    a perf-counter delta in a function that
                            touches jax but never syncs
                            (``block_until_ready``/``device_get``):
                            async dispatch makes the bracket measure
                            dispatch, not execution.
- NDS104 prefix-hash        content fingerprint over a sliced prefix
                            (``arr[:n].tobytes()``): same-shape changes
                            past the prefix serve stale cache entries
                            (round-5 `_register_staged` finding).
- NDS105 dead-field         dataclass field written but never read
                            anywhere in the tree (round-5 `_DistTrace`
                            finding).
- NDS106 mutable-default    mutable function-argument default.
- NDS107 bare-except        ``except:`` catching SystemExit/
                            KeyboardInterrupt.
- NDS108 naked-retry        a retry loop (loop + except handler) that
                            sleeps a CONSTANT between attempts (no
                            backoff) or spins ``while True`` (no
                            attempt cap): under real contention a
                            fixed-interval uncapped retry herd is the
                            outage amplifier — use
                            ``resilience.retry.RetryPolicy``.
- NDS109 non-atomic-json    ``json.dump`` into a handle opened ``"w"``
                            on the final path, in a function that never
                            calls ``os.replace``/``os.rename``: a crash
                            mid-write leaves a TORN report/journal/
                            manifest a later reader crashes on or —
                            worse — half-trusts. Write via
                            ``io.integrity.write_json_atomic`` (tmp +
                            rename), or waive with why a torn read is
                            impossible for that artifact.

- NDS110 direct-executor    constructing a placement executor
                            (``DeviceExecutor(`` / ``ChunkedExecutor(``
                            / ``DistributedExecutor(`` /
                            ``CpuExecutor(``) in engine/suite code
                            outside ``engine/scheduler.py`` or the
                            executor's own defining module: placement
                            is a scheduling decision owned by the
                            unified pipeline, and a stray direct
                            construction silently regresses the
                            unification (no shared retry/ladder/
                            consensus wiring runs for it).

- NDS111 uncached-compile   ``jax.jit(...)`` or a ``.lower(args)``
                            AOT-lowering call inside ``engine/`` /
                            ``parallel/``: every lower+compile must
                            route through ``nds_tpu/cache/aot.py`` so
                            the persistent plan cache sees it — a
                            stray inline compile is invisible to the
                            cache and pays the full XLA bill in every
                            process. Sites that only BUILD the traced
                            callable (the ``jax.jit(fn)`` handed to
                            ``cache.aot``) carry waivers saying so.

- NDS112 int64-emulation-hazard
                            ``jnp.argsort`` / ``jnp.sort`` /
                            ``jnp.searchsorted`` in ``engine/`` /
                            ``parallel/`` with no explicit int32 cast
                            in the call: under x64 these carry int64
                            operands (argsort's implicit iota is the
                            canonical trap — see ``_build_lookup``),
                            and TPU emulates 64-bit sorts at ~4-8x the
                            native i32 cost. Narrow explicitly
                            (``_narrow_key`` / ``.astype(jnp.int32)``)
                            or waive with why the width is required.

- NDS113 direct-profiler    ``jax.profiler.start_trace`` outside
                            ``obs/profile.py``: profiler captures must
                            route through the trigger policy so the
                            single-active-trace invariant holds, the
                            capture lands in the BenchReport
                            ``profile`` block, and the on-stall hook
                            can always grab the profiler — a stray
                            start_trace wedges all of that.

- NDS114 unchained-signal-handler
                            ``signal.signal(...)`` installing a real
                            handler without the enclosing scope ever
                            calling ``signal.getsignal``: the install
                            silently DISCARDS whatever handler was
                            there — the flight-dump chain
                            (obs/fleet._install_sigterm) or the
                            preemption drain
                            (resilience/drain.DrainManager), both of
                            which capture and chain/restore the
                            previous handler (the blessed pattern).
                            Restores to ``SIG_DFL``/``SIG_IGN`` are
                            clean; anything else needs the chain or a
                            waiver saying why replacement is intended.
- NDS119 unjournaled-mutation
                            a direct store into a ``.tables[...]`` /
                            ``.columns[...]`` catalog (subscript
                            assign/del, or ``.pop/.setdefault/
                            .update/.clear`` on it) outside the
                            journaled machinery (engine/session.py,
                            engine/dml.py, columnar/delta.py,
                            io/host_table.py). Warehouse mutation
                            must flow through Session.register_table
                            or the DML path so the maintenance commit
                            journal, delta segments and table-scoped
                            plan invalidation all observe it — a raw
                            catalog write is invisible to crash
                            recovery and serves stale cached plans.

Waivers are per-line: ``# ndslint: waive[NDS1xx] -- justification`` on
the offending line or the line directly above. The justification is
mandatory; a waiver without one, or one that matches no violation, is
itself an error. The lightweight ``# ndslint: disable=NDS1xx`` form
(note optional, same staleness rules) suppresses per rule at sites
whose exemption is obvious in context — test helpers mainly; both
forms are shared verbatim by ndsraces and ndsjit markers. The marker
and file roots come from ``[tool.ndslint]`` in pyproject.toml
(tools/ndslint.py loads it).
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field


@dataclass
class LintViolation:
    rule: str
    path: str
    line: int
    msg: str
    waived: bool = False
    waiver_note: str = ""

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.msg}"


@dataclass
class Waiver:
    line: int           # line the waiver covers
    rules: list
    note: str
    used: bool = False


# ------------------------------------------------------------- waivers

# one waiver grammar, three tools: ndslint (this module's rules),
# ndsraces (nds_tpu/analysis/concurrency.py), and ndsjit
# (nds_tpu/analysis/jit_hazards.py) share the marker syntax differing
# only in the tool name, so the waiver-report and the stale-waiver
# semantics stay identical across all gates. Two per-line forms:
#
#   <line>  # <tool>: waive[NDS1xx] -- justification   (note mandatory)
#   <line>  # <tool>: disable=NDS1xx[,NDSyyy]          (note optional)
#
# ``waive[...]`` is the audited form — the justification is part of
# the record; ``disable=`` is the lightweight per-rule suppression for
# sites whose exemption is obvious in context (test helpers,
# fixtures). Both cover the next line when standalone, both go stale
# (and fail the gate) when they match no live finding.
WAIVER_RE = re.compile(
    r"#\s*ndslint:\s*waive\[(?P<rules>[A-Z0-9, ]+)\]"
    r"(?:\s*--\s*(?P<note>.*\S))?")

_WAIVER_RES: dict = {"ndslint": WAIVER_RE}
_DISABLE_RES: dict = {}


def waiver_re(tool: str) -> "re.Pattern":
    pat = _WAIVER_RES.get(tool)
    if pat is None:
        pat = _WAIVER_RES[tool] = re.compile(
            r"#\s*" + re.escape(tool)
            + r":\s*waive\[(?P<rules>[A-Z0-9, ]+)\]"
            r"(?:\s*--\s*(?P<note>.*\S))?")
    return pat


def disable_re(tool: str) -> "re.Pattern":
    pat = _DISABLE_RES.get(tool)
    if pat is None:
        pat = _DISABLE_RES[tool] = re.compile(
            r"#\s*" + re.escape(tool)
            + r":\s*disable=(?P<rules>[A-Z0-9]+(?:\s*,\s*[A-Z0-9]+)*)"
            r"(?:\s*--\s*(?P<note>.*\S))?")
    return pat


def _comment_tokens(src: str):
    """[(line, standalone, comment_text)] for each COMMENT token, or
    None when the source does not tokenize (caller falls back to the
    raw line scan — a best-effort net for broken sources the ast
    parse will report anyway)."""
    try:
        out = []
        for t in tokenize.generate_tokens(io.StringIO(src).readline):
            if t.type != tokenize.COMMENT:
                continue
            row, col = t.start
            line = t.line if t.line else ""
            out.append((row, line[:col].strip() == "", t.string))
        return out
    except (tokenize.TokenError, IndentationError, SyntaxError,
            ValueError):
        return None


def parse_waivers(src: str, tool: str = "ndslint",
                  meta_rule: str = "NDS100"
                  ) -> "tuple[dict, list[LintViolation]]":
    """{covered_line: Waiver} plus violations for malformed waivers.
    A marker on its own line covers the next line; an end-of-line
    marker covers its own. ``tool`` picks the marker (``ndslint`` /
    ``ndsraces`` / ``ndsjit``); ``meta_rule`` is the rule id
    malformed-waiver errors report under. ``waive[...]`` requires a
    ``-- justification``; ``disable=NDS1xx`` does not (its note is
    optional) — both forms share staleness accounting."""
    waivers: dict[int, Waiver] = {}
    errors: list[LintViolation] = []
    lines = src.splitlines()
    # only genuine COMMENT tokens carry markers: a marker spelled
    # inside a string literal (linter test fixtures embed whole
    # sources, markers included) must not parse as a waiver of the
    # embedding file — tokenize separates the two exactly. Sources
    # that don't tokenize fall back to the raw per-line scan.
    candidates = _comment_tokens(src)
    if candidates is None:
        candidates = [(i, None, text)
                      for i, text in enumerate(lines, 1)]
    for lineno, standalone, text in candidates:
        m = waiver_re(tool).search(text)
        need_note = True
        if not m:
            m = disable_re(tool).search(text)
            need_note = False
        if not m:
            continue
        rules = [r.strip() for r in m.group("rules").split(",")
                 if r.strip()]
        note = (m.group("note") or "").strip()
        if standalone is None:
            standalone = text[: m.start()].strip() == ""
        covered = lineno + 1 if standalone else lineno
        if need_note and not note:
            errors.append(LintViolation(
                meta_rule, "", lineno,
                f"waiver without justification (use "
                f"'# {tool}: waive[...] -- why', or the per-rule "
                f"'# {tool}: disable=NDS1xx' form)"))
            continue
        waivers[covered] = Waiver(covered, rules, note)
    return waivers, errors


def waiver_report(results: "dict[str, LintResult]",
                  verbose: bool = False) -> "list[str]":
    """Tree-wide waiver hygiene report shared by ``ndslint
    --waiver-report`` and ``ndsraces --waiver-report``: per-rule waiver
    counts per tool, each waiver's site + note under ``verbose``, and
    every STALE waiver (one matching no live finding — already a gate
    error) flagged explicitly so audits see exactly what to drop."""
    lines: list[str] = []
    for tool in sorted(results):
        res = results[tool]
        by_rule: dict[str, list] = {}
        for v in res.waived:
            by_rule.setdefault(v.rule, []).append(v)
        total = sum(len(vs) for vs in by_rule.values())
        lines.append(f"{tool}: {total} waiver(s) across "
                     f"{len(by_rule)} rule(s)")
        for rule in sorted(by_rule):
            vs = by_rule[rule]
            lines.append(f"  {rule}: {len(vs)}")
            if verbose:
                for v in sorted(vs, key=lambda x: (x.path, x.line)):
                    lines.append(f"    {v.path}:{v.line}: "
                                 f"{v.waiver_note}")
        stale = [e for e in res.errors
                 if "matches no violation" in e.msg]
        for e in sorted(stale, key=lambda x: (x.path, x.line)):
            lines.append(f"  STALE: {e.path}:{e.line}: {e.msg}")
    return lines


# --------------------------------------------------------------- rules

class Rule:
    id = "NDS000"
    name = "base"
    #: path substrings this rule is restricted to ([] = everywhere)
    paths: tuple = ()

    def applies(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return not self.paths or any(p in norm for p in self.paths)

    def check(self, tree: ast.AST, src: str,
              path: str) -> "list[LintViolation]":
        raise NotImplementedError


def _walk_funcs(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _names_in(node: ast.AST) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _attrs_in(node: ast.AST) -> set:
    return {n.attr for n in ast.walk(node)
            if isinstance(n, ast.Attribute)}


def _is_id_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "id")


class IdKeyedCacheRule(Rule):
    """NDS101: ``cache[id(x)] = v`` / ``cache.setdefault(id(x), v)``
    where nothing guarantees ``x`` outlives the entry. Detected
    syntactically (any id()-derived subscript store); sites that DO pin
    the object in the stored value carry a waiver saying so."""

    id = "NDS101"
    name = "id-keyed-cache"

    def check(self, tree, src, path):
        out = []
        # names assigned from a bare id(...) call anywhere in the file:
        # `nid = id(node); cache[nid] = v` is the same hazard spelled
        # in two statements (name collisions across scopes only widen
        # the net, which is the right failure mode for a linter)
        id_vars = {t.id for n in ast.walk(tree)
                   if isinstance(n, ast.Assign) and _is_id_call(n.value)
                   for t in n.targets if isinstance(t, ast.Name)}

        def keyed_by_id(expr: ast.AST) -> bool:
            return (any(_is_id_call(x) for x in ast.walk(expr))
                    or (isinstance(expr, ast.Name)
                        and expr.id in id_vars))

        for n in ast.walk(tree):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                tgts = (n.targets if isinstance(n, ast.Assign)
                        else [n.target])
                for t in tgts:
                    if (isinstance(t, ast.Subscript)
                            and keyed_by_id(t.slice)):
                        out.append(LintViolation(
                            self.id, path, n.lineno,
                            "store keyed by id(): a recycled address "
                            "can serve another object's entry unless "
                            "the value pins the object"))
            elif (isinstance(n, ast.Call)
                  and isinstance(n.func, ast.Attribute)
                  and n.func.attr == "setdefault" and n.args
                  and keyed_by_id(n.args[0])):
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    "id()-keyed setdefault: the stored value must pin "
                    "the keyed object (or waive with the pinning "
                    "argument)"))
        return out


class RawTimingRule(Rule):
    """NDS102: raw wall-clock reads in the engine/parallel layers."""

    id = "NDS102"
    name = "raw-timing"
    paths = ("nds_tpu/engine/", "nds_tpu/parallel/")
    _FUNCS = {"time", "perf_counter", "monotonic", "process_time"}

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in self._FUNCS
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id.lstrip("_") == "time"):
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    f"raw time.{n.func.attr}() in the engine layer — "
                    f"use an obs span (or waive with why the raw "
                    f"bracket is required)"))
        return out


class UnsyncedTimingRule(Rule):
    """NDS103: a perf-counter delta inside a function that references
    jax but never calls block_until_ready/device_get — with async
    dispatch the bracket closes before the device work does."""

    id = "NDS103"
    name = "unsynced-timing"
    paths = ("nds_tpu/engine/", "nds_tpu/parallel/")
    _JAX = {"jax", "jnp", "lax", "jitted", "shard_map"}
    _SYNC = {"block_until_ready", "device_get"}

    def check(self, tree, src, path):
        out = []
        for fn in _walk_funcs(tree):
            names = _names_in(fn)
            attrs = _attrs_in(fn)
            if not (names & self._JAX):
                continue
            if (names | attrs) & self._SYNC:
                continue
            timer_vars = set()
            for n in ast.walk(fn):
                if (isinstance(n, ast.Assign)
                        and isinstance(n.value, ast.Call)
                        and isinstance(n.value.func, ast.Attribute)
                        and n.value.func.attr == "perf_counter"):
                    timer_vars |= {t.id for t in n.targets
                                   if isinstance(t, ast.Name)}
            for n in ast.walk(fn):
                if not (isinstance(n, ast.BinOp)
                        and isinstance(n.op, ast.Sub)):
                    continue
                ends_bracket = any(
                    (isinstance(x, ast.Name) and x.id in timer_vars)
                    or (isinstance(x, ast.Call)
                        and isinstance(x.func, ast.Attribute)
                        and x.func.attr == "perf_counter")
                    for x in (n.left, n.right))
                if ends_bracket and timer_vars:
                    out.append(LintViolation(
                        self.id, path, n.lineno,
                        f"timing bracket in {fn.name}() closes without "
                        f"block_until_ready/device_get — async "
                        f"dispatch makes this measure dispatch, not "
                        f"execution"))
        return out


class PrefixHashRule(Rule):
    """NDS104: hashing a sliced array prefix (``arr[:n].tobytes()``)
    as a content fingerprint."""

    id = "NDS104"
    name = "prefix-hash"

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            if not (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "tobytes"):
                continue
            sliced = any(isinstance(x, ast.Subscript)
                         and isinstance(x.slice, ast.Slice)
                         for x in ast.walk(n.func.value))
            if sliced:
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    "content fingerprint over a sliced prefix: "
                    "same-shape changes past the slice serve stale "
                    "cache entries — hash the full buffer"))
        return out


class DeadDataclassFieldRule(Rule):
    """NDS105: a dataclass field no code ever reads. Reads counted
    tree-wide: attribute loads, keyword-free getattr-style string
    constants (``getattr(n, "child")`` walks via string names), so only
    fields dead under BOTH access styles flag. Needs the whole-tree
    index built by ``build_read_index``."""

    id = "NDS105"
    name = "dead-field"

    def __init__(self):
        self.reads: set = set()
        self.strings: set = set()

    def build_read_index(self, trees: "list[ast.AST]") -> None:
        for tree in trees:
            for n in ast.walk(tree):
                if (isinstance(n, ast.Attribute)
                        and isinstance(n.ctx, ast.Load)):
                    self.reads.add(n.attr)
                elif (isinstance(n, ast.Constant)
                      and isinstance(n.value, str)):
                    self.strings.add(n.value)

    @staticmethod
    def _is_dataclass(cls: ast.ClassDef) -> bool:
        for d in cls.decorator_list:
            target = d.func if isinstance(d, ast.Call) else d
            name = (target.attr if isinstance(target, ast.Attribute)
                    else getattr(target, "id", ""))
            if name == "dataclass":
                return True
        return False

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            if not (isinstance(n, ast.ClassDef)
                    and self._is_dataclass(n)):
                continue
            for stmt in n.body:
                if not (isinstance(stmt, ast.AnnAssign)
                        and isinstance(stmt.target, ast.Name)):
                    continue
                fname = stmt.target.id
                if fname.startswith("__"):
                    continue
                if fname in self.reads or fname in self.strings:
                    continue
                out.append(LintViolation(
                    self.id, path, stmt.lineno,
                    f"dataclass field {n.name}.{fname} is written but "
                    f"never read anywhere in the tree"))
        return out


class MutableDefaultRule(Rule):
    """NDS106: mutable default argument shared across calls."""

    id = "NDS106"
    name = "mutable-default"
    _CTORS = {"list", "dict", "set"}

    def check(self, tree, src, path):
        out = []
        for fn in _walk_funcs(tree):
            for d in list(fn.args.defaults) + [
                    x for x in fn.args.kw_defaults if x is not None]:
                bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in self._CTORS)
                if bad:
                    out.append(LintViolation(
                        self.id, path, d.lineno,
                        f"mutable default argument in {fn.name}()"))
        return out


class BareExceptRule(Rule):
    """NDS107: ``except:`` swallows SystemExit/KeyboardInterrupt."""

    id = "NDS107"
    name = "bare-except"

    def check(self, tree, src, path):
        return [LintViolation(self.id, path, n.lineno,
                              "bare except: catches SystemExit and "
                              "KeyboardInterrupt — name the exception")
                for n in ast.walk(tree)
                if isinstance(n, ast.ExceptHandler) and n.type is None]


class NakedRetryRule(Rule):
    """NDS108: hand-rolled retry loops. A loop whose body contains an
    ``except`` handler (the retry shape) flags when it either sleeps a
    constant interval (no backoff) or is ``while True`` with a sleep
    (no attempt cap). ``resilience.retry.RetryPolicy`` provides capped
    attempts + exponential backoff + jitter; loops that delegate to it
    (``policy.attempts()``, computed delays) don't match."""

    id = "NDS108"
    name = "naked-retry"

    @staticmethod
    def _is_sleep(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Name):
            return f.id == "sleep"
        return (isinstance(f, ast.Attribute) and f.attr == "sleep"
                and isinstance(f.value, ast.Name)
                and f.value.id.lstrip("_") == "time")

    def check(self, tree, src, path):
        out = []
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.While, ast.For)):
                continue
            has_except = any(isinstance(x, ast.ExceptHandler)
                             for x in ast.walk(loop))
            if not has_except:
                continue
            sleeps = [x for x in ast.walk(loop) if self._is_sleep(x)]
            if not sleeps:
                continue
            uncapped = (isinstance(loop, ast.While)
                        and isinstance(loop.test, ast.Constant)
                        and loop.test.value is True)
            if uncapped:
                out.append(LintViolation(
                    self.id, path, loop.lineno,
                    "while True retry loop with no attempt cap — use "
                    "resilience.retry.RetryPolicy (capped attempts + "
                    "backoff)"))
                continue
            for s in sleeps:
                if any(isinstance(a, ast.Constant) for a in s.args):
                    out.append(LintViolation(
                        self.id, path, s.lineno,
                        "retry loop sleeps a constant interval (no "
                        "backoff) — use resilience.retry.RetryPolicy "
                        "(exponential backoff + jitter)"))
        return out


class NonAtomicJsonWriteRule(Rule):
    """NDS109: ``json.dump(obj, f)`` where ``f`` was opened ``"w"``
    directly on the destination path and the enclosing function never
    calls ``os.replace``/``os.rename`` — the torn-artifact shape.
    Functions that DO rename are presumed to be writing a tmp file
    first (the journal/snapshot/integrity writers), so they don't
    flag."""

    id = "NDS109"
    name = "non-atomic-json-write"
    paths = ("nds_tpu/",)

    @staticmethod
    def _renames_atomically(fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in ("replace", "rename")
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id == "os"):
                return True
        return False

    @staticmethod
    def _write_handles(fn: ast.AST) -> set:
        """Names bound by ``with open(path, "w"...) as f``."""
        out = set()
        for n in ast.walk(fn):
            if not isinstance(n, ast.With):
                continue
            for item in n.items:
                c = item.context_expr
                if not (isinstance(c, ast.Call)
                        and isinstance(c.func, ast.Name)
                        and c.func.id == "open"):
                    continue
                mode = None
                if len(c.args) > 1 and isinstance(c.args[1],
                                                  ast.Constant):
                    mode = c.args[1].value
                for kw in c.keywords:
                    if (kw.arg == "mode"
                            and isinstance(kw.value, ast.Constant)):
                        mode = kw.value.value
                if (isinstance(mode, str) and "w" in mode
                        and isinstance(item.optional_vars, ast.Name)):
                    out.add(item.optional_vars.id)
        return out

    def check(self, tree, src, path):
        out = []
        for fn in _walk_funcs(tree):
            if self._renames_atomically(fn):
                continue
            handles = self._write_handles(fn)
            if not handles:
                continue
            for n in ast.walk(fn):
                if not (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)
                        and n.func.attr == "dump"
                        and isinstance(n.func.value, ast.Name)
                        and n.func.value.id == "json"):
                    continue
                fp = (n.args[1] if len(n.args) > 1
                      else next((kw.value for kw in n.keywords
                                 if kw.arg == "fp"), None))
                if isinstance(fp, ast.Name) and fp.id in handles:
                    out.append(LintViolation(
                        self.id, path, n.lineno,
                        "non-atomic JSON artifact write: json.dump "
                        "into open(.., 'w') without tmp+os.replace — "
                        "a crash leaves a torn file; use "
                        "io.integrity.write_json_atomic (or waive "
                        "with why a torn read is impossible)"))
        return out


class DirectExecutorRule(Rule):
    """NDS110: direct placement-executor construction outside the
    scheduler. The unified pipeline (engine/scheduler.py) is the one
    place executors are built — it wires the cost model, the
    degradation ladder, retries, and SPMD consensus around them. A
    direct ``DeviceExecutor(...)`` call elsewhere in nds_tpu/ runs none
    of that and silently regresses the unification. Each executor's own
    defining module is exempt (its ``make_*_factory`` helpers and
    subclass internals construct legitimately); tests and tools are out
    of scope by path."""

    id = "NDS110"
    name = "direct-executor"
    paths = ("nds_tpu/",)

    EXECUTORS = {
        "CpuExecutor": "cpu_exec",
        "DeviceExecutor": "device_exec",
        "ChunkedExecutor": "chunked_exec",
        "DistributedExecutor": "dist_exec",
    }
    ALLOWED = ("engine/scheduler.py",)

    def check(self, tree, src, path):
        norm = path.replace("\\", "/")
        if any(a in norm for a in self.ALLOWED):
            return []
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = (f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute)
                    else None)
            home = self.EXECUTORS.get(name or "")
            if home is None or norm.endswith(f"{home}.py"):
                continue
            out.append(LintViolation(
                self.id, path, node.lineno,
                f"direct {name} construction outside "
                f"engine/scheduler.py — placement is a scheduling "
                f"decision; route through the ExecutionPipeline (or "
                f"waive with why this site must bypass it)"))
        return out


class UncachedCompileRule(Rule):
    """NDS111: an XLA compile entry point — ``jax.jit(...)`` or an AOT
    ``.lower(args)`` chain — inside ``engine/``/``parallel/`` outside
    the cache module. The persistent plan cache (nds_tpu/cache/) can
    only serve a program it saw compiled: ``cache.aot`` is the single
    lower/compile site, so every executor program gets the
    consult-hit-or-persist treatment. ``.lower()`` with no arguments
    is string-lowercasing, never flagged; ``jax.jit(fn)`` used purely
    to build the traced callable handed to ``cache.aot`` is
    legitimate and carries a waiver saying so."""

    id = "NDS111"
    name = "uncached-compile"
    paths = ("nds_tpu/engine/", "nds_tpu/parallel/")

    def check(self, tree, src, path):
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "jit"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "jax"):
                out.append(LintViolation(
                    self.id, path, node.lineno,
                    "jax.jit in engine/parallel code — compiles must "
                    "route through nds_tpu/cache/aot.py so the "
                    "persistent plan cache sees them (waive sites "
                    "that only build the traced callable)"))
            elif (isinstance(f, ast.Attribute) and f.attr == "lower"
                    and (node.args or node.keywords)
                    and not self._string_module(f.value)):
                # .lower(bufs) is jax AOT lowering; bare .lower() is a
                # string method
                out.append(LintViolation(
                    self.id, path, node.lineno,
                    ".lower(args) AOT chain in engine/parallel code — "
                    "use cache.aot.lower_and_compile / cached_compile "
                    "so the plan cache can serve and persist the "
                    "executable"))
        return out

    @staticmethod
    def _string_module(value: ast.AST) -> bool:
        """``np.char.lower(a)`` / ``str.lower(s)`` are string ops, not
        AOT lowering — a function call THROUGH a string-handling
        module, distinguishable syntactically from a method on a
        jitted object."""
        if isinstance(value, ast.Name):
            return value.id == "str"
        if isinstance(value, ast.Attribute):
            return value.attr == "char"
        return False


class Int64EmulationHazardRule(Rule):
    """NDS112: ``jnp.argsort``/``jnp.sort``/``jnp.searchsorted`` call
    in the engine/parallel layers whose call text carries no explicit
    int32 narrowing. Under ``jax_enable_x64`` the default integer (and
    argsort's implicit index operand) is int64, which TPU sorts via
    emulation at a multiple of the native i32 cost — the trap
    ``_build_lookup``'s explicit-iota comment documents, promoted to a
    rule. The check is textual-per-call on purpose: an ``int32``
    mention anywhere in the call (an ``astype``, a ``dtype=``, a
    ``_narrow_key``-produced name is NOT enough — narrowing helpers
    live a line above) signals the author handled the width; anything
    else needs a waiver explaining why 64-bit operands are required."""

    id = "NDS112"
    name = "int64-emulation-hazard"
    paths = ("nds_tpu/engine/", "nds_tpu/parallel/")
    _FUNCS = {"argsort", "sort", "searchsorted"}

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            if not (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in self._FUNCS
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id == "jnp"):
                continue
            seg = ast.get_source_segment(src, n) or ""
            if "int32" in seg:
                continue
            out.append(LintViolation(
                self.id, path, n.lineno,
                f"jnp.{n.func.attr}() without an explicit int32 cast: "
                f"int64 operands under x64 push the sort/search onto "
                f"TPU's emulated 64-bit path (narrow via _narrow_key/"
                f".astype(jnp.int32), or waive with why the width is "
                f"required)"))
        return out


class DirectProfilerRule(Rule):
    """NDS113: a ``jax.profiler.start_trace`` call outside
    ``obs/profile.py``. The profiler allows one active trace per
    process; the profile module owns that invariant (trigger policy,
    BenchReport ``profile`` block, the watchdog's on-stall capture),
    and a stray start_trace elsewhere wedges every managed capture
    after it. Route through ``obs.profile`` (``stream_trace`` /
    ``Profiler.capture``) instead."""

    id = "NDS113"
    name = "direct-profiler"
    paths = ("nds_tpu/", "tools/")
    ALLOWED = ("obs/profile.py",)

    def check(self, tree, src, path):
        norm = path.replace("\\", "/")
        if any(a in norm for a in self.ALLOWED):
            return []
        out = []
        for n in ast.walk(tree):
            if not (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "start_trace"):
                continue
            v = n.func.value
            via_profiler = (
                (isinstance(v, ast.Attribute) and v.attr == "profiler")
                or (isinstance(v, ast.Name) and v.id == "profiler"))
            if via_profiler:
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    "direct jax.profiler.start_trace outside "
                    "obs/profile.py — captures must route through the "
                    "profile trigger policy (obs.profile.stream_trace "
                    "/ Profiler.capture), or waive with why this site "
                    "must own the profiler"))
        return out


class UnchainedSignalHandlerRule(Rule):
    """NDS114: a ``signal.signal(sig, handler)`` call whose enclosing
    scope never calls ``signal.getsignal``. Installing a handler
    without capturing the previous one silently discards it — in this
    tree that means losing the SIGTERM flight-dump chain
    (obs/fleet.py) or the preemption drain (resilience/drain.py),
    whose chaining installs are the blessed pattern. Restoring
    ``SIG_DFL``/``SIG_IGN`` (the re-raise idiom inside a handler) is
    clean by design."""

    id = "NDS114"
    name = "unchained-signal-handler"
    paths = ("nds_tpu/",)

    @staticmethod
    def _is_restore(arg: ast.AST) -> bool:
        if isinstance(arg, ast.Attribute):
            return arg.attr in ("SIG_DFL", "SIG_IGN")
        return (isinstance(arg, ast.Name)
                and arg.id in ("SIG_DFL", "SIG_IGN"))

    @staticmethod
    def _has_getsignal(node: ast.AST) -> bool:
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if (isinstance(f, ast.Attribute)
                    and f.attr == "getsignal") \
                    or (isinstance(f, ast.Name)
                        and f.id == "getsignal"):
                return True
        return False

    def check(self, tree, src, path):
        out = []
        funcs = list(_walk_funcs(tree))
        for n in ast.walk(tree):
            if not (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "signal"
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id.lstrip("_") == "signal"
                    and len(n.args) >= 2):
                continue
            if self._is_restore(n.args[1]):
                continue
            # chained when ANY enclosing function (nearest or an
            # ancestor closure that captured prev) calls getsignal;
            # module-level installs check the whole module
            enclosing = [f for f in funcs
                         if any(ch is n for ch in ast.walk(f))]
            if enclosing:
                if any(self._has_getsignal(f) for f in enclosing):
                    continue
            elif self._has_getsignal(tree):
                continue
            out.append(LintViolation(
                self.id, path, n.lineno,
                "signal.signal() discards the previous handler (no "
                "signal.getsignal in scope): chain it like the "
                "flight-dump/drain installs (obs/fleet.py, "
                "resilience/drain.py), or waive with why replacement "
                "is intended"))
        return out


class BlockingInAsyncRule(Rule):
    """NDS115: blocking calls inside a coroutine of the serving layer
    (``nds_tpu/serve/``). The asyncio front shares ONE event loop
    across every connection: a ``time.sleep``, a synchronous ``open``,
    a ``subprocess``/``socket``/``requests`` call, or a concurrent
    ``Future.result()`` inside an ``async def`` stalls every in-flight
    request at once. Engine work belongs on the engine thread; a
    coroutine may only enqueue and ``await`` (``asyncio.wrap_future``
    is the blessed bridge)."""

    id = "NDS115"
    name = "blocking-in-async"
    paths = ("nds_tpu/serve/",)
    _MODULE_CALLS = {"subprocess": {"run", "call", "check_output",
                                    "check_call", "Popen"},
                     "socket": {"socket", "create_connection"},
                     "requests": {"get", "post", "put", "delete",
                                  "request"},
                     "time": {"sleep"}}

    def _violation_for(self, n: ast.Call) -> "str | None":
        f = n.func
        if isinstance(f, ast.Name) and f.id == "open":
            return "synchronous open() blocks the event loop"
        if isinstance(f, ast.Attribute):
            if (isinstance(f.value, ast.Name)
                    and f.attr in self._MODULE_CALLS.get(
                        f.value.id.lstrip("_"), ())):
                return (f"{f.value.id}.{f.attr}() blocks the event "
                        f"loop")
            if f.attr == "result":
                return ("Future.result() blocks the event loop — "
                        "await asyncio.wrap_future(fut) instead")
        return None

    @staticmethod
    def _body_nodes(fn: ast.AST):
        """The coroutine's own statements: nested defs run wherever
        they're CALLED, not on the loop, so their bodies are pruned
        (nested ASYNC defs get their own check via _walk_funcs)."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield n
            stack.extend(ast.iter_child_nodes(n))

    def check(self, tree, src, path):
        out = []
        for fn in _walk_funcs(tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            for n in self._body_nodes(fn):
                if not isinstance(n, ast.Call):
                    continue
                why = self._violation_for(n)
                if why:
                    out.append(LintViolation(
                        self.id, path, n.lineno,
                        f"{why} (in coroutine {fn.name!r}): hand the "
                        f"work to the engine thread, or waive with "
                        f"why blocking here is safe"))
        return out


class EarlyMaterializationRule(Rule):
    """NDS116: decoding dictionary codes to string bytes inside the
    engine/parallel dataflow outside the result compactor. The
    columnar contract (nds_tpu/columnar/; README "Compressed columnar
    store") is LATE materialization: operators consume int32 codes /
    packed words end-to-end and values materialize exactly once, at
    ``_materialize``. A ``col.decode()`` call or a
    ``something.dictionary[...]`` gather anywhere else in the engine
    re-inflates a column to full width mid-plan — the exact bytes the
    compressed store exists to never move. The CPU oracle
    (``engine/cpu_exec.py``) and host-side DML (``engine/dml.py``)
    materialize BY CONTRACT (they are the host reference semantics,
    not device dataflow) and are exempt by path; host-side *planning*
    uses elsewhere carry waivers saying so."""

    id = "NDS116"
    name = "early-materialization"
    paths = ("nds_tpu/engine/", "nds_tpu/parallel/")
    ALLOWED = ("engine/cpu_exec.py", "engine/dml.py")

    @staticmethod
    def _in_materialize(funcs: list, node: ast.AST) -> bool:
        for f in funcs:
            if f.name in ("_materialize", "materialize") and any(
                    ch is node for ch in ast.walk(f)):
                return True
        return False

    def check(self, tree, src, path):
        norm = path.replace("\\", "/")
        if any(a in norm for a in self.ALLOWED):
            return []
        out = []
        funcs = list(_walk_funcs(tree))
        for n in ast.walk(tree):
            hit = None
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "decode"
                    and not n.args and not n.keywords):
                hit = (".decode() materializes a dictionary column "
                       "to python values")
            elif (isinstance(n, ast.Subscript)
                    and isinstance(n.value, ast.Attribute)
                    and n.value.attr == "dictionary"):
                hit = (".dictionary[...] gathers string bytes "
                       "through the dictionary")
            if hit is None or self._in_materialize(funcs, n):
                continue
            out.append(LintViolation(
                self.id, path, n.lineno,
                f"{hit} outside the result compactor — the engine "
                f"operates on codes end-to-end (late "
                f"materialization, nds_tpu/columnar/); decode at "
                f"_materialize, or waive with why this site is "
                f"host-side planning, not dataflow"))
        return out


class BlockingTransferInStreamLoopRule(Rule):
    """NDS117: a blocking device->host transfer inside the chunked
    engine's phase-A stream loops or the prefetch worker. The pipelined
    executor (``engine/pipeline_io.py``; README "Pipelined execution")
    exists so host staging overlaps device compute; a stray
    ``jax.device_get(...)`` (bare, or through the executors'
    ``_readback``), ``.block_until_ready()``, or
    ``np.asarray(<device result>)`` inside a chunk loop serializes the
    pipeline right back to the pre-overlap behavior — silently, since
    results stay correct and only occupancy collapses. The two
    SANCTIONED per-chunk sync points (the partial-agg overflow verdict,
    the keep-mask readback — each IS the loop's product) carry waivers
    saying so; anything new must justify why its sync cannot move to a
    chunk boundary."""

    id = "NDS117"
    name = "blocking-transfer-in-stream-loop"
    paths = ("engine/chunked_exec.py", "engine/pipeline_io.py")

    def check(self, tree, src, path):
        out = []
        seen: set = set()
        loops = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.For, ast.While))]
        for loop in loops:
            for n in ast.walk(loop):
                if id(n) in seen or not isinstance(n, ast.Call):
                    continue
                f = n.func
                hit = None
                if isinstance(f, ast.Attribute):
                    if f.attr == "device_get":
                        hit = "jax.device_get(...)"
                    elif f.attr == "block_until_ready":
                        hit = ".block_until_ready()"
                    elif f.attr == "_readback":
                        # the executors' device.readback helper: one
                        # jax.device_get, counted
                        hit = "._readback(...)"
                    elif (f.attr == "asarray"
                          and isinstance(f.value, ast.Name)
                          and f.value.id in ("np", "numpy")
                          and n.args
                          and isinstance(n.args[0], ast.Call)):
                        # np.asarray over a CALL result (a device
                        # computation) syncs; slicing host arrays
                        # (np.asarray(col.values[...])) does not
                        hit = "np.asarray(<device result>)"
                if hit is None:
                    continue
                seen.add(id(n))
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    f"{hit} inside a chunk-stream loop blocks the "
                    f"prefetch pipeline (transfers must stay async — "
                    f"jax.device_put — with syncs only at sanctioned "
                    f"per-chunk read-back points); move the sync to a "
                    f"chunk boundary or waive with why this sync is "
                    f"the loop's product"))
        return out


class UndeadlinedAwaitRule(Rule):
    """NDS118: an ``await`` on a cross-process send/recv/drain inside
    the serving layer (``nds_tpu/serve/``) without an enclosing
    deadline. The fleet router and the TCP front await sockets owned
    by OTHER processes — a replica that was SIGKILLed mid-response, a
    client that stopped reading — and an unbounded ``await
    reader.readline()`` / ``writer.drain()`` / ``wait_closed()`` /
    ``asyncio.open_connection()`` pins a coroutine (and whatever
    request it carries) on that dead peer forever. Every such await
    must sit under ``asyncio.wait_for(...)`` or an enclosing ``async
    with asyncio.timeout(...)`` block, so failover latency is a
    config knob, not a hang."""

    id = "NDS118"
    name = "undeadlined-await"
    paths = ("nds_tpu/serve/",)
    _STREAM_ATTRS = {"readline", "readexactly", "readuntil", "read",
                     "drain", "wait_closed"}

    @classmethod
    def _stream_call(cls, call: ast.Call) -> "str | None":
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr in cls._STREAM_ATTRS:
                return f".{f.attr}()"
            if (f.attr == "open_connection"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "asyncio"):
                return "asyncio.open_connection()"
        return None

    @staticmethod
    def _under_timeout(node: ast.AST) -> bool:
        """An enclosing ``async with asyncio.timeout(...)`` (or
        ``timeout_at``) bounds every await in its body; the search
        stops at the coroutine boundary — an outer function's timeout
        does not cover a nested def that runs elsewhere."""
        cur = getattr(node, "_nds118_parent", None)
        while cur is not None:
            if isinstance(cur, ast.AsyncWith):
                for item in cur.items:
                    c = item.context_expr
                    if (isinstance(c, ast.Call)
                            and isinstance(c.func, ast.Attribute)
                            and c.func.attr in ("timeout",
                                                "timeout_at")
                            and isinstance(c.func.value, ast.Name)
                            and c.func.value.id == "asyncio"):
                        return True
            if isinstance(cur, (ast.FunctionDef,
                                ast.AsyncFunctionDef)):
                break
            cur = getattr(cur, "_nds118_parent", None)
        return False

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            for ch in ast.iter_child_nodes(n):
                ch._nds118_parent = n
        for fn in _walk_funcs(tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            for n in BlockingInAsyncRule._body_nodes(fn):
                if (not isinstance(n, ast.Await)
                        or not isinstance(n.value, ast.Call)):
                    continue
                what = self._stream_call(n.value)
                if what is None or self._under_timeout(n):
                    continue
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    f"await {what} without a deadline (in coroutine "
                    f"{fn.name!r}): one dead peer must never hang "
                    f"the serving front — wrap in "
                    f"asyncio.wait_for(...) or an enclosing "
                    f"asyncio.timeout(...) block, or waive with why "
                    f"this await is bounded elsewhere"))
        return out


class UnjournaledMutationRule(Rule):
    """NDS119: a raw store into a ``.tables[...]`` / ``.columns[...]``
    catalog outside the journaled machinery. The writable warehouse
    keeps three views consistent — the session catalog, the delta
    segments/deleted-masks (columnar/delta.py) and the maintenance
    commit journal (nds/maintenance.py) — and ALL of them hang off the
    blessed mutation paths: ``Session.register_table``, the DML
    ``sess.sql`` route and the delta append/delete helpers. A direct
    subscript write (or ``.pop``/``.update``/``.setdefault``/
    ``.clear`` on the catalog dict) bypasses table-scoped plan
    invalidation and crash recovery: cached plans keep serving the old
    table and a resumed run can double-apply or lose the mutation."""

    id = "NDS119"
    name = "unjournaled-mutation"
    paths = ("nds_tpu/",)
    _CATALOGS = ("tables", "columns")
    #: the machinery the journal/invalidation contract is BUILT from —
    #: mutation here is the blessed path itself
    _ALLOWED = ("nds_tpu/engine/session.py", "nds_tpu/engine/dml.py",
                "nds_tpu/columnar/delta.py", "nds_tpu/io/host_table.py")
    _MUTATORS = {"pop", "setdefault", "update", "clear"}

    def applies(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        if any(norm.endswith(a) for a in self._ALLOWED):
            return False
        return super().applies(path)

    @classmethod
    def _catalog_attr(cls, node: ast.AST) -> bool:
        return (isinstance(node, ast.Attribute)
                and node.attr in cls._CATALOGS)

    def check(self, tree, src, path):
        out = []
        for n in ast.walk(tree):
            targets = []
            if isinstance(n, ast.Assign):
                targets = n.targets
            elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
                targets = [n.target]
            elif isinstance(n, ast.Delete):
                targets = n.targets
            for t in targets:
                if (isinstance(t, ast.Subscript)
                        and self._catalog_attr(t.value)):
                    out.append(LintViolation(
                        self.id, path, n.lineno,
                        f"direct .{t.value.attr}[...] catalog write "
                        f"bypasses the DML journal and table-scoped "
                        f"invalidation: route through "
                        f"Session.register_table / the sess.sql DML "
                        f"path / columnar.delta, or waive with why "
                        f"this store is journal-invisible by design"))
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in self._MUTATORS
                    and self._catalog_attr(n.func.value)):
                out.append(LintViolation(
                    self.id, path, n.lineno,
                    f".{n.func.value.attr}.{n.func.attr}(...) mutates "
                    f"a catalog dict outside the journaled machinery: "
                    f"route through Session.register_table / the "
                    f"sess.sql DML path / columnar.delta, or waive "
                    f"with why this store is journal-invisible by "
                    f"design"))
        return out


def default_rules() -> "list[Rule]":
    return [IdKeyedCacheRule(), RawTimingRule(), UnsyncedTimingRule(),
            PrefixHashRule(), DeadDataclassFieldRule(),
            MutableDefaultRule(), BareExceptRule(), NakedRetryRule(),
            NonAtomicJsonWriteRule(), DirectExecutorRule(),
            UncachedCompileRule(), Int64EmulationHazardRule(),
            DirectProfilerRule(), UnchainedSignalHandlerRule(),
            BlockingInAsyncRule(), EarlyMaterializationRule(),
            BlockingTransferInStreamLoopRule(),
            UndeadlinedAwaitRule(), UnjournaledMutationRule()]


# -------------------------------------------------------------- driver

@dataclass
class LintResult:
    violations: list = field(default_factory=list)  # unwaived, to fix
    waived: list = field(default_factory=list)      # waived, informational
    errors: list = field(default_factory=list)      # malformed/unused waivers


def lint_sources(sources: "dict[str, str]",
                 rules: "list[Rule] | None" = None,
                 enabled: "set[str] | None" = None,
                 tool: str = "ndslint",
                 meta_rule: str = "NDS100") -> LintResult:
    """Lint {path: source}. Rules needing a whole-tree read index (dead
    fields) see every file; violations and waiver bookkeeping are
    per-file. ``enabled`` filters by rule id (None = all). ``tool`` /
    ``meta_rule`` select the waiver marker and the id malformed/stale
    waivers report under — ndsjit (jit_hazards.py) drives this same
    loop with its own catalog."""
    rules = default_rules() if rules is None else rules
    if enabled is not None:
        rules = [r for r in rules if r.id in enabled]
    res = LintResult()
    trees: dict[str, ast.AST] = {}
    for path, src in sorted(sources.items()):
        try:
            trees[path] = ast.parse(src)
        except SyntaxError as exc:
            res.errors.append(LintViolation(
                "NDS000", path, exc.lineno or 0,
                f"syntax error: {exc.msg}"))
    for r in rules:
        if isinstance(r, DeadDataclassFieldRule):
            r.build_read_index(list(trees.values()))
    for path, tree in trees.items():
        src = sources[path]
        waivers, werrs = parse_waivers(src, tool=tool,
                                       meta_rule=meta_rule)
        for w in werrs:
            w.path = path
            res.errors.append(w)
        for r in rules:
            if not r.applies(path):
                continue
            for v in r.check(tree, src, path):
                w = waivers.get(v.line)
                if w is not None and v.rule in w.rules:
                    w.used = True
                    v.waived = True
                    v.waiver_note = w.note
                    res.waived.append(v)
                else:
                    res.violations.append(v)
        for w in waivers.values():
            if not w.used:
                res.errors.append(LintViolation(
                    meta_rule, path, w.line,
                    f"waiver for {','.join(w.rules)} matches no "
                    f"violation — stale, remove it"))
    return res
