"""Whole-surface extraction: the shared registry the closure rules consume.

One walk over the project collects every *string-keyed surface* the cluster
is steered by — metric instrumentation and read sites, conf-key reads with
their defaults, ``RAYDP_TPU_*`` env reads — plus every name the docs claim
exists (markdown table rows in ``docs/*.md``). The registry rules
(metric-registry / conf-registry / env-registry) then check the two-way
closure: a name written in one place and read in another is a contract, and
a typo'd metric is a controller silently steering on nothing
["Bugs as Deviant Behavior", Engler et al. 2001].

Dynamic names are kept as *patterns*: an f-string hole becomes a ``<*>``
segment wildcard (``f"tenant.{ns}.bytes_stored"`` -> ``tenant.<*>.bytes_stored``),
matching the docs' own placeholder convention (``tenant.<ns>.bytes_stored``).
The time-series layer's fan-out suffixes (``.max``/``.p50``/``.p99``/
``.delta``/``.count``/``.sum``/``.mean``/``.min``) are stripped before
read->write matching so a scrape-side read of ``serve.ttft_ms.p99`` resolves
to the ``serve.ttft_ms`` histogram.

Everything here is stdlib-only (ast + re) so the analyzer keeps running
before dependency install in CI.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# name shapes and matching
# ---------------------------------------------------------------------------

# dotted metric name (holes already normalized to <*>)
_METRIC_SHAPE = re.compile(r"^[a-z][a-z0-9_]*(\.([a-z0-9_]+|<\*>))+$")
# conf keys allow camelCase segments (etl.dynamicAllocation.maxMemPressure)
_CONF_SHAPE = re.compile(r"^[a-z][A-Za-z0-9_]*(\.[A-Za-z0-9_]+)+$")
_ENV_SHAPE = re.compile(r"^RAYDP_TPU_[A-Z0-9_]+$")

# suffixes the time-series layer fans out of one instrument — a read of
# <name>.<suffix> is a read of <name>
FANOUT_SUFFIXES = ("max", "min", "p50", "p99", "count", "sum", "mean", "delta")

_WILD = "<*>"


def pattern_regex(pattern: str) -> "re.Pattern":
    """Compile a name pattern (``<*>`` = exactly one dotted segment) to a
    regex. Docs placeholders (``<ns>``, ``<role>``, ``<method>``, ...) are
    normalized to ``<*>`` before this is called."""
    parts = [
        r"[^.]+" if seg == _WILD else re.escape(seg)
        for seg in pattern.split(".")
    ]
    return re.compile(r"\.".join(parts) + r"\Z")


def _probe(pattern: str) -> str:
    """A concrete example name for ``pattern`` (holes become one segment)."""
    return pattern.replace(_WILD, "xWILDx")


def patterns_match(a: str, b: str) -> bool:
    """True when the two name patterns can describe the same metric: either
    regex covers the other's example form (wildcards unify)."""
    if a == b:
        return True
    return bool(
        pattern_regex(a).match(_probe(b)) or pattern_regex(b).match(_probe(a))
    )


def strip_fanout(name: str) -> str:
    head, _, tail = name.rpartition(".")
    if head and tail in FANOUT_SUFFIXES:
        return head
    return name


# ---------------------------------------------------------------------------
# record types
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MetricUse:
    pattern: str           # name pattern, holes as <*>
    mode: str              # "write" | "read" | "mention"
    kind: str              # counter/gauge/histogram/query/get/subscript/wrapper
    path: str
    line: int


@dataclasses.dataclass
class ConfRead:
    key: str
    has_default: bool
    path: str
    line: int


@dataclasses.dataclass
class EnvUse:
    name: str
    mode: str              # "read" | "set"
    path: str
    line: int


@dataclasses.dataclass
class DocEntry:
    name: str              # pattern (placeholders normalized to <*>)
    kind: str              # "metric" | "conf" | "env"
    path: str
    line: int


class DocFile:
    """One markdown file: text, table rows, and raydp-lint suppressions
    (HTML-comment form: ``<!-- raydp-lint: disable=metric-registry -->``)."""

    def __init__(self, path: str, display_path: str, text: str):
        self.path = path
        self.display_path = display_path
        self.lines = text.splitlines()
        self._line_suppressions: Dict[int, Set[str]] = {}
        self._file_suppressions: Set[str] = set()
        marker = re.compile(
            r"raydp-lint:\s*disable(?P<scope>-file)?=(?P<rules>[A-Za-z0-9_,\- ]+)"
        )
        for i, line in enumerate(self.lines):
            m = marker.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group("rules").split(",") if r.strip()}
            if m.group("scope"):
                self._file_suppressions |= rules
            else:
                self._line_suppressions.setdefault(i + 1, set()).update(rules)

    def is_suppressed(self, rule: str, line: int) -> bool:
        if rule in self._file_suppressions or "all" in self._file_suppressions:
            return True
        rules = self._line_suppressions.get(line, ())
        return rule in rules or "all" in rules


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


class Surfaces:
    def __init__(self):
        self.metric_writes: List[MetricUse] = []
        self.metric_reads: List[MetricUse] = []      # strong reads
        self.metric_mentions: List[MetricUse] = []   # dict-get / wrapper reads
        self.dynamic_metric_sites: List[Tuple[str, int, str]] = []
        self.conf_reads: List[ConfRead] = []
        self.env_reads: List[EnvUse] = []
        self.env_sets: List[EnvUse] = []
        self.env_consts: Dict[str, str] = {}         # CONST name -> var value
        self.doc_metrics: List[DocEntry] = []
        self.doc_confs: List[DocEntry] = []
        self.doc_envs: List[DocEntry] = []
        self.doc_files: Dict[str, DocFile] = {}
        # full-surface mode: the project under analysis includes both the
        # package and the tools readers, so doc-side (dead-row) and
        # whole-program checks are meaningful. Partial sweeps (one
        # subdirectory) only get code-side checks.
        self.full_surface: bool = False

    # -- derived views ----------------------------------------------------

    def write_patterns(self) -> List[str]:
        seen, out = set(), []
        for w in self.metric_writes:
            if w.pattern not in seen:
                seen.add(w.pattern)
                out.append(w.pattern)
        return out

    def write_families(self) -> Set[str]:
        return {w.pattern.split(".", 1)[0] for w in self.metric_writes}

    def conf_keys(self) -> Set[str]:
        return {c.key for c in self.conf_reads}

    def doc_conf_keys(self) -> Set[str]:
        return {d.name for d in self.doc_confs}

    def has_writer(self, read_pattern: str) -> bool:
        name = read_pattern
        for candidate in (name, strip_fanout(name)):
            for w in self.metric_writes:
                if patterns_match(candidate, w.pattern):
                    return True
        return False

    def is_documented_metric(self, write_pattern: str) -> bool:
        return any(
            patterns_match(write_pattern, d.name) for d in self.doc_metrics
        )


# ---------------------------------------------------------------------------
# python-side extraction
# ---------------------------------------------------------------------------

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}
_METRIC_WRITE_ATTRS = {"inc", "set", "observe", "set_watermark"}
_METRIC_READ_ATTRS = {"value", "quantile", "snapshot"}
_QUERY_FUNCS = {"query_metrics", "windowed_local", "windowed"}
_CONF_RECEIVERS = {"configs", "conf", "cfg", "merged"}
# receivers whose .get("a.b") is definitely NOT a metric lookup
_NON_METRIC_RECEIVERS = _CONF_RECEIVERS | {
    "environ", "kwargs", "opts", "labels", "args", "os",
}


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _name_patterns(node: ast.AST) -> List[str]:
    """Resolve a metric-name expression to name patterns. Literal -> itself;
    f-string -> holes as <*> (a hole mid-segment widens to the segment);
    conditional -> both arms. [] = dynamic/unresolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.JoinedStr):
        buf = []
        for part in node.values:
            if isinstance(part, ast.Constant) and isinstance(part.value, str):
                buf.append(part.value)
            else:
                buf.append(_WILD)
        raw = "".join(buf)
        # a hole glued to text inside one segment (e.g. "lineage_{k}")
        # widens that whole segment to <*>
        segs = [
            _WILD if _WILD in seg else seg for seg in raw.split(".")
        ]
        return [".".join(segs)]
    if isinstance(node, ast.IfExp):
        return _name_patterns(node.body) + _name_patterns(node.orelse)
    return []


def _parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _call_default(call: ast.Call) -> bool:
    """Does this ``.get(key, ...)``-shaped call pass an explicit default?"""
    if len(call.args) >= 2:
        return True
    return any(kw.arg == "default" for kw in call.keywords)


@dataclasses.dataclass
class _ConfWrapper:
    prefix: str
    param: str
    param_has_default: bool


def _conf_wrapper_of(fn: ast.AST) -> Optional[_ConfWrapper]:
    """Detect a local conf-read wrapper: a function whose body calls
    ``<conf-ish>.get(param)`` or ``<conf-ish>.get(f"prefix{param}")``.
    Covers the session's ``_flag(name, default)`` helper and
    serve/config.py's ``get(key, default)`` (prefix ``serve.``)."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    params = fn.args.args
    if not params:
        return None
    first = params[0].arg
    if first in ("self", "cls"):
        if len(params) < 2:
            return None
        first = params[1].arg
    n_defaults = len(fn.args.defaults)
    # does the param after the key param (conventionally "default") or the
    # key param's own position carry a default? we only need to know whether
    # a call relying on wrapper defaults still "declares" one: any default
    # on the wrapper's second parameter counts
    has_default_param = n_defaults >= 1
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if not isinstance(node.func, ast.Attribute) or node.func.attr != "get":
            continue
        recv = _dotted(node.func.value) or ""
        if recv.rsplit(".", 1)[-1] not in _CONF_RECEIVERS:
            continue
        if not node.args:
            continue
        key = node.args[0]
        if isinstance(key, ast.Name) and key.id == first:
            return _ConfWrapper("", first, has_default_param)
        if isinstance(key, ast.JoinedStr) and len(key.values) == 2:
            pre, hole = key.values
            if (
                isinstance(pre, ast.Constant)
                and isinstance(pre.value, str)
                and isinstance(hole, ast.FormattedValue)
                and isinstance(hole.value, ast.Name)
                and hole.value.id == first
            ):
                return _ConfWrapper(pre.value, first, has_default_param)
    return None


def _get_wrapper_of(fn: ast.AST) -> bool:
    """Detect a generic lookup wrapper: single-key function whose body
    subscripts/``.get``s an arbitrary mapping with its first param (a
    ``total(name)`` over dump_metrics snapshots). Calls with literal args
    become metric *mentions*."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    params = [a.arg for a in fn.args.args if a.arg not in ("self", "cls")]
    if not params:
        return False
    first = params[0]
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id == first
            ):
                return True
        elif isinstance(node, ast.Subscript):
            sl = node.slice
            if isinstance(sl, ast.Name) and sl.id == first:
                return True
    return False


def _extract_python(src, surfaces: Surfaces) -> None:
    tree = src.tree
    if tree is None:
        return
    parents = _parent_map(tree)
    path, add = src.display_path, None

    # module-level env-name constants: NAME = "RAYDP_TPU_X"
    for node in tree.body if hasattr(tree, "body") else []:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            and _ENV_SHAPE.match(node.value.value)
        ):
            surfaces.env_consts[node.targets[0].id] = node.value.value

    # wrapper discovery (per file)
    conf_wrappers: Dict[str, _ConfWrapper] = {}
    get_wrappers: Set[str] = set()
    # registry aliases: `m = obs.metrics` makes `m.counter(...)` a write
    metric_aliases: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cw = _conf_wrapper_of(node)
            if cw is not None:
                conf_wrappers[node.name] = cw
            elif _get_wrapper_of(node):
                get_wrappers.add(node.name)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            value = _dotted(node.value) or ""
            if (
                isinstance(target, ast.Name)
                and value.rsplit(".", 1)[-1] == "metrics"
            ):
                metric_aliases.add(target.id)

    def resolve_env_arg(arg: ast.AST) -> Optional[str]:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value if _ENV_SHAPE.match(arg.value) else None
        if isinstance(arg, ast.Name):
            return surfaces.env_consts.get(arg.id)
        if isinstance(arg, ast.Attribute):  # common.SESSION_ENV style
            return surfaces.env_consts.get(arg.attr)
        return None

    for node in ast.walk(tree):
        # ---- metric factory calls: <...metrics>.counter|gauge|histogram(n)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            recv = _dotted(node.func.value) or ""
            recv_last = recv.rsplit(".", 1)[-1]
            if (
                attr in _METRIC_FACTORIES
                and ("metrics" in recv_last or recv_last in metric_aliases)
                and node.args
            ):
                pats = _name_patterns(node.args[0])
                parent = parents.get(node)
                mode = "write"
                if isinstance(parent, ast.Attribute):
                    if parent.attr in _METRIC_READ_ATTRS:
                        mode = "read"
                    elif parent.attr in _METRIC_WRITE_ATTRS:
                        mode = "write"
                if not pats:
                    surfaces.dynamic_metric_sites.append(
                        (path, node.lineno, mode)
                    )
                for p in pats:
                    use = MetricUse(p, mode, attr, path, node.lineno)
                    (surfaces.metric_writes if mode == "write"
                     else surfaces.metric_reads).append(use)

            # ---- windowed/query reads
            elif attr in _QUERY_FUNCS and node.args:
                for p in _name_patterns(node.args[0]):
                    surfaces.metric_reads.append(
                        MetricUse(p, "read", "query", path, node.lineno)
                    )

            # ---- dict-style lookups: X.get("a.b.c", ...)
            elif attr == "get" and node.args:
                key = node.args[0]
                lit = (
                    key.value
                    if isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                    else None
                )
                if recv_last in ("environ", "os.environ") or recv.endswith(
                    "os.environ"
                ):
                    env = resolve_env_arg(key)
                    if env:
                        surfaces.env_reads.append(
                            EnvUse(env, "read", path, node.lineno)
                        )
                elif lit is not None and "." in lit:
                    if recv_last in _CONF_RECEIVERS:
                        if _CONF_SHAPE.match(lit):
                            surfaces.conf_reads.append(
                                ConfRead(
                                    lit, _call_default(node), path, node.lineno
                                )
                            )
                    elif (
                        recv_last not in _NON_METRIC_RECEIVERS
                        and _METRIC_SHAPE.match(lit)
                    ):
                        surfaces.metric_mentions.append(
                            MetricUse(lit, "mention", "get", path, node.lineno)
                        )

        # ---- plain-call wrappers: _flag("planner.x"), get("max_retries"),
        #      total("rpc.bytes_over_wire")
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            fname, lit = node.func.id, node.args[0].value
            if fname in conf_wrappers:
                cw = conf_wrappers[fname]
                key = cw.prefix + lit
                if _CONF_SHAPE.match(key):
                    surfaces.conf_reads.append(
                        ConfRead(
                            key,
                            _call_default(node) or cw.param_has_default,
                            path,
                            node.lineno,
                        )
                    )
            elif fname in get_wrappers and _METRIC_SHAPE.match(lit):
                if "." in lit:
                    surfaces.metric_mentions.append(
                        MetricUse(lit, "mention", "wrapper", path, node.lineno)
                    )

        # ---- os.getenv(...)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, (ast.Name, ast.Attribute))
        ):
            fdot = _dotted(node.func) or ""
            if fdot.rsplit(".", 1)[-1] == "getenv" and node.args:
                env = resolve_env_arg(node.args[0])
                if env:
                    surfaces.env_reads.append(
                        EnvUse(env, "read", path, node.lineno)
                    )

        # ---- environ["X"] loads/stores, env-dict stores, setdefault/pop
        if isinstance(node, ast.Subscript):
            # synthesized metrics: snapshot["trace.spans_dropped"] = {...}
            # (the head injects per-process series into a scrape snapshot)
            if isinstance(node.ctx, ast.Store):
                key_lit = (
                    node.slice.value
                    if isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)
                    else None
                )
                recv = _dotted(node.value) or ""
                recv_last = recv.rsplit(".", 1)[-1]
                if (
                    key_lit
                    and _METRIC_SHAPE.match(key_lit)
                    and ("metric" in recv_last or "snapshot" in recv_last)
                ):
                    surfaces.metric_writes.append(
                        MetricUse(key_lit, "write", "dict", path, node.lineno)
                    )
            env = resolve_env_arg(node.slice)
            if env:
                recv = _dotted(node.value) or ""
                is_environ = recv.endswith("environ")
                if isinstance(node.ctx, ast.Store):
                    surfaces.env_sets.append(
                        EnvUse(env, "set", path, node.lineno)
                    )
                elif is_environ:
                    surfaces.env_reads.append(
                        EnvUse(env, "read", path, node.lineno)
                    )
                else:
                    # a literal RAYDP_TPU_* subscript on an arbitrary dict
                    # (child-process env assembly) still references the var
                    surfaces.env_sets.append(
                        EnvUse(env, "set", path, node.lineno)
                    )
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr in ("setdefault", "pop") and node.args:
                recv = _dotted(node.func.value) or ""
                if recv.endswith("environ"):
                    env = resolve_env_arg(node.args[0])
                    if env:
                        surfaces.env_reads.append(
                            EnvUse(env, "read", path, node.lineno)
                        )
        # ---- "RAYDP_TPU_X" in os.environ
        if isinstance(node, ast.Compare) and node.ops:
            if isinstance(node.ops[0], (ast.In, ast.NotIn)):
                recv = _dotted(node.comparators[0]) or ""
                if recv.endswith("environ"):
                    env = resolve_env_arg(node.left)
                    if env:
                        surfaces.env_reads.append(
                            EnvUse(env, "read", path, node.lineno)
                        )
        # ---- dict-literal env keys (spawner env dicts)
        if isinstance(node, ast.Dict):
            for k in node.keys:
                if (
                    isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                    and _ENV_SHAPE.match(k.value)
                ):
                    surfaces.env_sets.append(
                        EnvUse(k.value, "set", path, node.lineno)
                    )


# ---------------------------------------------------------------------------
# docs-side extraction
# ---------------------------------------------------------------------------

_METRIC_HEADERS = {"metric", "series"}
_CONF_HEADERS = {"knob", "key", "conf", "conf key", "option", "setting", "env",
                 "variable", "env var"}
_PLACEHOLDER = re.compile(r"<[A-Za-z_][A-Za-z0-9_]*>")
_BACKTICK = re.compile(r"`([^`]+)`")
_ENV_NAME = re.compile(r"RAYDP_TPU_[A-Z0-9_]+")


def _cells(line: str) -> List[str]:
    if not line.strip().startswith("|"):
        return []
    return [c.strip() for c in line.strip().strip("|").split("|")]


def _expand_braces(token: str) -> List[str]:
    m = re.search(r"\{([^{}]+)\}", token)
    if not m:
        return [token]
    head, tail = token[: m.start()], token[m.end():]
    out: List[str] = []
    for alt in m.group(1).split(","):
        out.extend(_expand_braces(head + alt.strip() + tail))
    return out


def _doc_cell_names(cell: str, shape: "re.Pattern") -> List[str]:
    """Name patterns from a table row's first cell. Handles brace fan-out,
    ``<ns>``-style placeholders, ``(+`.max`)`` annotations, and leading-dot
    shorthand (``.veto.slots`` / ``.max_replicas`` continues the previous
    name by replacing its last k segments)."""
    names: List[str] = []
    for token in _BACKTICK.findall(cell):
        token = token.strip()
        for t in _expand_braces(token):
            t = _PLACEHOLDER.sub(_WILD, t)
            if t.startswith("."):
                segs = [s for s in t[1:].split(".") if s]
                if segs and all(s in FANOUT_SUFFIXES for s in segs):
                    continue  # fan-out annotation, not a name
                if not names or not segs:
                    continue
                base = names[-1].split(".")
                if len(base) > len(segs):
                    names.append(".".join(base[: -len(segs)] + segs))
                continue
            if shape.match(t):
                names.append(t)
    return names


def _extract_doc(doc: DocFile, surfaces: Surfaces) -> None:
    lines = doc.lines
    table_kind: Optional[str] = None
    expect_sep = False
    for i, line in enumerate(lines):
        lineno = i + 1
        cells = _cells(line)
        if not cells:
            table_kind = None
            expect_sep = False
        elif expect_sep:
            expect_sep = False
            if not set("".join(cells)) <= set("-: "):
                table_kind = None
        elif table_kind is None:
            header = cells[0].lower().strip("`*")
            if header in _METRIC_HEADERS:
                table_kind = "metric"
                expect_sep = True
            elif header in _CONF_HEADERS:
                table_kind = "conf"
                expect_sep = True
        else:
            first = cells[0]
            if table_kind == "metric":
                for name in _doc_cell_names(first, _METRIC_SHAPE):
                    surfaces.doc_metrics.append(
                        DocEntry(name, "metric", doc.display_path, lineno)
                    )
            else:
                for token in _BACKTICK.findall(first):
                    token = token.strip()
                    if _ENV_SHAPE.match(token):
                        surfaces.doc_envs.append(
                            DocEntry(token, "env", doc.display_path, lineno)
                        )
                for name in _doc_cell_names(first, _CONF_SHAPE):
                    if not _ENV_SHAPE.match(name):
                        surfaces.doc_confs.append(
                            DocEntry(name, "conf", doc.display_path, lineno)
                        )
        # env vars are "documented" by ANY backticked mention in the docs —
        # tables are preferred but an inline mention (`RAYDP_TPU_X=1` or
        # an expression containing the name) is still a contract
        for span in _BACKTICK.findall(line):
            for env in _ENV_NAME.findall(span):
                surfaces.doc_envs.append(
                    DocEntry(env, "env", doc.display_path, lineno)
                )


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

# the files whose presence in the project means "the whole surface is in
# scope": the metric registry itself plus the tools' reader side (the trace
# analyzer CLI). Doc-side dead-row checks and whole-program
# read-without-writer checks only run then — a partial sweep of one
# subdirectory must not flag every doc row as dead.
_FULL_SURFACE_MARKERS = ("raydp_tpu/obs/metrics.py", "tools/trace_analyze.py")

DOC_GLOBS = ("docs",)


def extract(project, root: Optional[str] = None) -> Surfaces:
    surfaces = Surfaces()
    root = root or getattr(project, "root", None) or os.getcwd()

    present = {f.display_path.replace(os.sep, "/") for f in project}
    surfaces.full_surface = all(m in present for m in _FULL_SURFACE_MARKERS)

    for src in project:
        _extract_python(src, surfaces)
    # second pass: env-const resolution is global (SESSION_ENV defined in
    # cluster/common.py, read via `from ... import SESSION_ENV` elsewhere) —
    # re-run the env extraction once all consts are known
    if surfaces.env_consts:
        surfaces.env_reads.clear()
        surfaces.env_sets.clear()
        for src in project:
            _extract_env_only(src, surfaces)

    docs_dir = os.path.join(root, "docs")
    doc_paths: List[str] = []
    if os.path.isdir(docs_dir):
        doc_paths = [
            os.path.join(docs_dir, n)
            for n in sorted(os.listdir(docs_dir))
            if n.endswith(".md")
        ]
    readme = os.path.join(root, "README.md")
    if os.path.isfile(readme):
        doc_paths.append(readme)
    for p in doc_paths:
        try:
            with open(p, encoding="utf-8", errors="replace") as f:
                text = f.read()
        except OSError:  # raydp-lint: disable=swallowed-exceptions (doc vanished mid-scan; registry checks simply see fewer rows)
            continue
        display = os.path.relpath(p, root)
        doc = DocFile(p, display, text)
        surfaces.doc_files[display] = doc
        _extract_doc(doc, surfaces)
    return surfaces


def _extract_env_only(src, surfaces: Surfaces) -> None:
    """Env extraction with the complete cross-module const map (subset of
    :func:`_extract_python`; metric/conf surfaces are not touched)."""
    tree = src.tree
    if tree is None:
        return
    path = src.display_path

    def resolve(arg: ast.AST) -> Optional[str]:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value if _ENV_SHAPE.match(arg.value) else None
        if isinstance(arg, ast.Name):
            return surfaces.env_consts.get(arg.id)
        if isinstance(arg, ast.Attribute):
            return surfaces.env_consts.get(arg.attr)
        return None

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fdot = _dotted(node.func) or ""
            last = fdot.rsplit(".", 1)[-1]
            if last == "getenv" and node.args:
                env = resolve(node.args[0])
                if env:
                    surfaces.env_reads.append(
                        EnvUse(env, "read", path, node.lineno)
                    )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in ("get", "setdefault", "pop")
                and node.args
            ):
                recv = _dotted(node.func.value) or ""
                if recv.endswith("environ"):
                    env = resolve(node.args[0])
                    if env:
                        surfaces.env_reads.append(
                            EnvUse(env, "read", path, node.lineno)
                        )
        elif isinstance(node, ast.Subscript):
            env = resolve(node.slice)
            if env:
                if isinstance(node.ctx, ast.Store):
                    surfaces.env_sets.append(
                        EnvUse(env, "set", path, node.lineno)
                    )
                else:
                    recv = _dotted(node.value) or ""
                    mode = "read" if recv.endswith("environ") else "set"
                    (surfaces.env_reads if mode == "read"
                     else surfaces.env_sets).append(
                        EnvUse(env, mode, path, node.lineno)
                    )
        elif isinstance(node, ast.Compare) and node.ops:
            if isinstance(node.ops[0], (ast.In, ast.NotIn)):
                recv = _dotted(node.comparators[0]) or ""
                if recv.endswith("environ"):
                    env = resolve(node.left)
                    if env:
                        surfaces.env_reads.append(
                            EnvUse(env, "read", path, node.lineno)
                        )
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                if (
                    isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                    and _ENV_SHAPE.match(k.value)
                ):
                    surfaces.env_sets.append(
                        EnvUse(k.value, "set", path, node.lineno)
                    )
