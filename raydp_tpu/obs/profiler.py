"""Compute observatory: step profiler, capture windows, memory watermarks.

PR 14 made the control plane observable; this module watches the COMPUTE
plane — the half of the system ROADMAP item 2's kernel work will be
measured with (the xprof/JAX-profiler role in the TPU ecosystem, the
Dapper-style complement to request tracing). Three pieces:

- **Step profiler** (:class:`StepPhaseRecorder`): always-on per-step phase
  decomposition of a fit — host decode/ingest wait, H2D upload, the host's
  time inside a compiled call (``dispatch``: the call returns before the
  device finishes, so this is never device time), device sync — feeding
  ``estimator.step.{ingest,h2d,dispatch,sync}_ms`` histograms into the PR 14
  TSDB (scrapeable mid-fit), and the count of steps the device has
  FINISHED, read without a fence from the returned loss handles
  (``estimator.steps_completed``). The instruments are the registry's
  lock-free histograms; ``RAYDP_TPU_STEP_PROFILER=0`` turns the recorder
  into a shared no-op.
- **Capture window** (:class:`CaptureWindow` / :func:`profile_fit`): an
  on-demand deep capture — wraps ``jax.profiler`` start/stop_trace when
  the backend supports it, and ALWAYS collects the obs span records of the
  wrapped region (span-only capture is the CPU fallback, never a failure).
  Artifacts land under :func:`artifacts_dir` (gitignored ``artifacts/``).
- **Memory watermark plane** (:func:`sample_memory`): per-process RSS,
  /dev/shm namespace live bytes, device live-array bytes, and a
  ``mem.pressure`` fraction — sampled on the existing obs flush ticks (the
  tracing layer calls :func:`sample_memory` before every snapshot ship),
  recorded as high-watermark gauges so the TSDB carries both the live
  value and the peak (``mem.rss_bytes`` / ``mem.rss_bytes.max`` series).
  Crash dossiers attach the per-process ``mem.*`` tails; the elasticity
  and serve-autoscaler controllers read ``mem.pressure`` before growing.
- **Device scopes** (:func:`device_scope` / :func:`note_program` /
  :func:`device_scopes`): the one way to name a region of a compiled
  program, the list of those names, and, for whoever asks, what every
  instruction of the compiled programs still alive belongs to. A device
  trace's events carry an instruction's HLO line and no scope; joined to
  this map by instruction name they give device time by scope.
- **Compile account** (:func:`install_compile_listeners` /
  :func:`compile_account`): what ``jax.monitoring`` reports of every compile
  in the process (trace, lowering, XLA's compile or the cache's load), kept
  by the ``estimator.compile`` site open on the reporting thread, by the
  function's name where no site is open, and once more where it comes after
  a fit's first fence.

Stdlib-only at import (jax strictly on demand, and NEVER imported by the
memory sampler — a ``python -S`` worker without jax must flush cleanly).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import re
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from raydp_tpu.obs.metrics import metrics

STEP_PROFILER_ENV = "RAYDP_TPU_STEP_PROFILER"
ARTIFACTS_DIR_ENV = "RAYDP_TPU_ARTIFACTS_DIR"
JAX_PROFILER_ENV = "RAYDP_TPU_JAX_PROFILER"

STEP_PHASES = ("ingest", "h2d", "dispatch", "sync")

_step_profiler_on = os.environ.get(STEP_PROFILER_ENV, "1") not in (
    "0", "false", "False"
)


def step_profiler_enabled() -> bool:
    return _step_profiler_on


def set_step_profiler(on: bool) -> None:
    """Bench/test hook (the ``fit_profile_probe`` A/B arm); prefer the env
    var so spawned processes agree."""
    global _step_profiler_on
    _step_profiler_on = bool(on)


def artifacts_dir(*sub: str) -> str:
    """The gitignored artifact root (``artifacts/`` or
    ``RAYDP_TPU_ARTIFACTS_DIR``), with optional subdirs, created on
    demand — bench traces, profiler captures, and tool outputs all land
    here instead of littering the repo root."""
    root = os.environ.get(ARTIFACTS_DIR_ENV, "artifacts")
    path = os.path.join(root, *sub) if sub else root
    os.makedirs(path, exist_ok=True)
    return path


# ---------------------------------------------------------------------------
# step profiler
# ---------------------------------------------------------------------------


class _NoopRecorder:
    """Shared do-nothing recorder for the disabled arm: the per-step call
    sites stay branch-free (one attr call, two pass statements)."""

    __slots__ = ()
    enabled = False
    steps = 0
    steps_dispatched = 0
    drained = False

    def note(self, phase: str, seconds: float, steps: int = 1) -> None:
        pass

    def dispatched(self, seconds, handle, steps: int = 1) -> None:
        pass

    def poll(self):
        return 0, 0.0

    def count_tokens(self, tokens_per_step: int) -> None:
        pass

    def open_restart(self, epoch: int) -> None:
        pass

    def totals(self) -> Dict[str, float]:
        return {}


_NOOP_RECORDER = _NoopRecorder()

# in-flight dispatches remembered for poll(): entries carry CUMULATIVE step
# counts, so one that falls off the left end loses nothing — the next ready
# handle advances the count past it
_INFLIGHT_MAX = 1024


class StepPhaseRecorder:
    """Accumulates one fit's per-step phase decomposition, and counts the
    steps the device has finished.

    ``note(phase, seconds, steps)`` charges ``seconds`` of wall time to a
    phase across ``steps`` train steps: the runners call it once per
    segment with ``steps=S`` (the histogram then records the per-step
    average for that segment — the honest granularity when S steps ride one
    dispatch). Instruments are resolved ONCE (a note is a float add + a
    lock-free histogram observe).

    ``dispatched(seconds, handle, steps)`` follows every compiled call: the
    host's ``seconds`` inside the call go to phase ``dispatch`` (observed
    per DISPATCH, not per step: that is what the host pays), and ``handle``
    — the loss array the call returned — is kept with the cumulative step
    count. ``poll()`` pops the heads whose ``is_ready()`` is true, which
    blocks nothing, and so learns how far the device has got:
    ``estimator.steps_completed``. Dispatched minus completed is the
    host's run-ahead. Safe from any thread."""

    __slots__ = ("enabled", "steps", "drained", "_totals", "_hists", "_lock",
                 "_inflight", "steps_dispatched", "_completed", "_seen_at",
                 "_completed_counter", "_restart", "_restart_hist",
                 "_tokens_per_step", "_tokens_counter")

    def __init__(self):
        self.enabled = True
        self.steps = 0
        # the last thing the fit did to the device was wait for it (a sync
        # fence with no dispatch since): an epoch that ends so has a closing
        # fence even without an evaluation
        self.drained = False
        self._totals = {phase: 0.0 for phase in STEP_PHASES}
        self._hists = {
            phase: metrics.histogram(f"estimator.step.{phase}_ms")
            for phase in STEP_PHASES
        }
        self._lock = threading.Lock()
        self._inflight: "collections.deque" = collections.deque(
            maxlen=_INFLIGHT_MAX
        )
        self.steps_dispatched = 0
        self._completed = 0
        self._seen_at = time.perf_counter()
        self._completed_counter = metrics.counter("estimator.steps_completed")
        self._restart = None
        self._restart_hist = metrics.histogram("estimator.epoch.restart_ms")
        self._tokens_per_step = 0
        self._tokens_counter = None

    def count_tokens(self, tokens_per_step: int) -> None:
        """A fit whose steps each train ``tokens_per_step`` tokens:
        ``estimator.tokens_completed`` advances with
        ``estimator.steps_completed``, from the same observation."""
        self._tokens_per_step = int(tokens_per_step)
        if self._tokens_per_step:
            self._tokens_counter = metrics.counter("estimator.tokens_completed")

    def note(self, phase: str, seconds: float, steps: int = 1) -> None:
        if seconds < 0.0:
            seconds = 0.0
        self._totals[phase] += seconds
        if phase == "dispatch":
            self.steps += steps
            steps = 1
        self._hists[phase].observe(seconds / max(steps, 1) * 1000.0)
        if phase == "sync":
            # an existing fence just returned: everything dispatched is done
            self.drained = True
            self.poll()

    def dispatched(self, seconds: float, handle: Any,
                   steps: int = 1) -> None:
        """A compiled call returned ``handle`` after ``seconds`` on the
        host."""
        self.note("dispatch", seconds, steps)
        self.drained = False
        restart = self._restart
        if restart is not None:
            # the first dispatch after an epoch's closing fence is back: the
            # device has work again
            self._restart = None
            restart.finish()
            self._restart_hist.observe(restart.duration * 1000.0)
        with self._lock:
            self.steps_dispatched += steps
            self._inflight.append((self.steps_dispatched, handle))
        self.poll()

    def poll(self):
        """(steps the device has finished, ``perf_counter`` when the last
        advance was seen). Never blocks: ``jax.Array.is_ready()``. Steps
        finish in dispatch order, so a handle a later step consumed
        (donated: deleted) is dropped and its steps are counted with the
        next ready one."""
        with self._lock:
            inflight = self._inflight
            done = None
            while inflight:
                count, handle = inflight[0]
                try:
                    ready = handle.is_ready()
                except RuntimeError:
                    ready = None  # donated to a later step: deleted
                if ready is False:
                    break
                if ready:
                    done = count
                inflight.popleft()
            if done is not None:
                self._completed_counter.inc(done - self._completed)
                if self._tokens_counter is not None:
                    self._tokens_counter.inc(
                        (done - self._completed) * self._tokens_per_step
                    )
                self._completed = done
                self._seen_at = time.perf_counter()
            return self._completed, self._seen_at

    def open_restart(self, epoch: int) -> None:
        """An epoch's closing fence has returned and another epoch follows:
        until the next dispatch returns the device provably has nothing to
        do because of the host (history append, checkpoint, permutation
        ship, queue get, dispatch). ``estimator.epoch.restart_ms`` takes one
        observation per epoch boundary, from the ``estimator.restart``
        span's one clock."""
        from raydp_tpu.obs import tracing

        self._restart = tracing.span("estimator.restart", epoch=epoch).start()

    def totals(self) -> Dict[str, float]:
        return dict(self._totals)


def step_recorder() -> Any:
    """A fresh recorder for one fit — or the shared no-op when the step
    profiler is off."""
    return StepPhaseRecorder() if _step_profiler_on else _NOOP_RECORDER


# ---------------------------------------------------------------------------
# capture window (on-demand deep profile)
# ---------------------------------------------------------------------------

_capture_lock = threading.Lock()
_armed_capture: Optional["CaptureWindow"] = None


def armed_capture() -> Optional["CaptureWindow"]:
    """The capture window the next (or current) fit should feed, if any —
    the estimator's step paths poll this once per fit."""
    return _armed_capture


class CaptureWindow:
    """On-demand deep capture of a compute region.

    Two modes share one class:

    - ``steps=None`` (the serve replica's ``profile()``): the window brackets
      the ``with`` body — jax trace starts at enter, stops at exit.
    - ``steps=N`` (``session.profile_fit``): the window ARMS itself; the
      estimator's step paths call :meth:`begin_steps` at the first step and
      :meth:`note_step` per step, and the jax trace stops after N steps
      while the fit runs on — a bounded capture of a steady-state slice.

    Either way the obs span records of the window are collected on the
    entering thread (span-only capture — the guaranteed floor when
    ``jax.profiler`` is unavailable, disabled via ``RAYDP_TPU_JAX_PROFILER=0``,
    or the backend refuses to trace) and written to
    ``<out_dir>/spans.json`` at exit, with ``device_scopes.json`` beside it
    (:func:`device_scopes` of the programs compiled inside the window and of
    those still alive: what the deep trace's events are joined to).
    ``result()`` summarizes."""

    def __init__(self, steps: Optional[int] = None,
                 out_dir: Optional[str] = None, jax_trace: bool = True):
        from raydp_tpu.obs import tracing

        self.steps = int(steps) if steps else None
        self.out_dir = out_dir or os.path.join(
            artifacts_dir("profiles"), time.strftime("%Y%m%dT%H%M%S")
        )
        self._want_jax = bool(jax_trace) and os.environ.get(
            JAX_PROFILER_ENV, "1"
        ) not in ("0", "false", "False")
        self._collector = tracing.collect()
        self.records: List[dict] = []
        self.jax_trace_dir: Optional[str] = None
        self._jax_active = False
        self._budget_done = False  # step budget exhausted: stay stopped
        self._seen_steps = 0
        self.path: Optional[str] = None
        # compiled programs noted while the window is armed (note_program):
        # alive until the window has written what their instructions belong to
        self.pinned: List[Any] = []
        self.device_scopes_path: Optional[str] = None

    # -- jax trace half --------------------------------------------------

    def _start_jax(self) -> None:
        if not self._want_jax or self._jax_active:
            return
        try:
            import jax

            trace_dir = os.path.join(self.out_dir, "jax_trace")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            self._jax_active = True
            self.jax_trace_dir = trace_dir
        except Exception:  # raydp-lint: disable=swallowed-exceptions (no jax / backend refuses to trace: span-only capture is the documented fallback)
            self._want_jax = False

    def _stop_jax(self) -> None:
        if not self._jax_active:
            return
        self._jax_active = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # raydp-lint: disable=swallowed-exceptions (a failed stop must not discard the span capture)
            self.jax_trace_dir = None

    # -- fit-step protocol (driven by the estimator) ---------------------

    def begin_steps(self) -> None:
        """First train step of the captured fit reached: start the deep
        trace (bounded by ``steps``). Called before EVERY dispatch by the
        segment paths — once the budget is spent this must stay a no-op,
        or the trace would restart/stop around every remaining segment."""
        if self.steps is not None and not self._budget_done:
            self._start_jax()

    def note_step(self, n: int = 1) -> None:
        if self.steps is None:
            return
        self._seen_steps += n
        if self._seen_steps >= self.steps and not self._budget_done:
            self._budget_done = True
            self._stop_jax()

    # -- context manager -------------------------------------------------

    def __enter__(self) -> "CaptureWindow":
        global _armed_capture
        with _capture_lock:
            if _armed_capture is not None:
                raise RuntimeError("another profiler capture is active")
            _armed_capture = self
        self.records = self._collector.__enter__()
        if self.steps is None:
            self._start_jax()
        return self

    def __exit__(self, *exc) -> bool:
        global _armed_capture
        self._stop_jax()
        self._collector.__exit__(*exc)
        with _capture_lock:
            if _armed_capture is self:
                _armed_capture = None
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(self.out_dir, "spans.json")
            with open(path, "w") as f:
                json.dump(self.records, f, default=str)
            self.path = path
            # what each instruction of the compiled programs belongs to: the
            # deep trace's events carry instruction names and no scope
            path = os.path.join(self.out_dir, "device_scopes.json")
            with open(path, "w") as f:
                json.dump(device_scopes(), f)
            self.device_scopes_path = path
        except OSError:  # raydp-lint: disable=swallowed-exceptions (a full disk must not fail the profiled fit; the records stay in memory)
            self.device_scopes_path = None
        self.pinned = []
        return False

    def result(self) -> dict:
        return {
            "out_dir": self.out_dir,
            "spans_path": self.path,
            "span_records": len(self.records),
            "jax_trace_dir": self.jax_trace_dir,
            "device_scopes_path": self.device_scopes_path,
            "steps_captured": self._seen_steps if self.steps else None,
        }


def profile_fit(steps: int = 16, out_dir: Optional[str] = None,
                jax_trace: bool = True) -> CaptureWindow:
    """Arm a bounded fit capture::

        with session.profile_fit(steps=32) as cap:
            estimator.fit_on_etl(df)
        print(cap.result())

    The deep (jax) trace covers the first ``steps`` train steps; the span
    capture covers the whole window."""
    return CaptureWindow(steps=steps, out_dir=out_dir, jax_trace=jax_trace)


def capture(out_dir: Optional[str] = None,
            jax_trace: bool = True) -> CaptureWindow:
    """Bracket-style capture (no step budget): used by the serve replica's
    ``profile()`` and any tool that wants one region deep-traced."""
    return CaptureWindow(steps=None, out_dir=out_dir, jax_trace=jax_trace)


# ---------------------------------------------------------------------------
# device scopes (what each instruction of a compiled program belongs to)
# ---------------------------------------------------------------------------

# every name a device scope was opened under in this process, in order: what
# a reader of an ``op_name`` treats as a scope. ``jit(..)``, ``jvp(..)``,
# ``transpose(..)``, ``checkpoint``, ``rematted_computation``, ``while`` /
# ``body`` / ``cond`` / ``branch_*`` and the primitive's own name at the end
# are not in it
_scope_names: Dict[str, None] = {}


def device_scope(name: str):
    """``jax.named_scope(name)``, with ``name`` noted as a device scope: the
    one way the estimator, the models and the ops name a region of a
    compiled program (``obs.device_scope``). The name lands in the
    ``op_name`` of every instruction traced under it, through ``jvp``,
    ``transpose`` and ``checkpoint``; it is noted when the scope is opened,
    at trace time, which a load from the compile cache does not skip."""
    import jax

    _scope_names[name] = None
    return jax.named_scope(name)


def registered_scopes() -> Tuple[str, ...]:
    """The names device scopes were opened under so far, in order."""
    return tuple(_scope_names)


class _Program:
    """A compiled program somebody may ask about: held WEAKLY (a strong
    reference would keep a finished fit's executables in device memory)."""

    __slots__ = ("what", "seq", "ref", "scopes")

    def __init__(self, what: str, seq: int, program: Any):
        self.what, self.seq = what, seq
        self.ref = weakref.ref(program, lambda _ref: _programs.pop(seq, None))
        self.scopes: Optional[Dict[str, dict]] = None  # read when asked


_programs: Dict[int, _Program] = {}
_program_seq = itertools.count(1)


def note_program(what: Any, program: Any) -> None:
    """Note a compiled program (a ``jax.stages.Compiled``: anything with
    ``as_text()`` that can be weakly referenced) under ``what``, the
    ``estimator.compile`` span's. One dictionary write; nothing reads the
    program until :func:`device_scopes` is asked, and it is forgotten when
    its owner drops it. While a capture window is armed it pins what is
    noted, so that the window can still write the map after the fit that
    owned the programs has returned."""
    seq = next(_program_seq)
    _programs[seq] = _Program(str(what), seq, program)
    capture = _armed_capture
    if capture is not None:
        capture.pinned.append(program)


def device_scopes() -> Dict[str, Dict[str, dict]]:
    """What each instruction of the compiled programs still alive belongs
    to: ``{"<what>#<n>": {instruction name: {"result": <result type>,
    "scopes": [outermost, ..., innermost registered scope]}}}``, ``n``
    counting the programs noted in this process. Covered: the entry
    computation and every called computation a device trace shows as events
    of its own (loop and conditional bodies, calls); NOT the insides of a
    fusion, which is one event: it belongs to the scopes of the instruction
    XLA names it by (its root, or the product it was built around; where
    that name carries none: the last fused instruction's that has any), and
    carries ``"mixed": True`` where a fused instruction's innermost scope is
    not among them. A Mosaic call or a copy the compiler inserted is an
    instruction like any other; one with no ``op_name``, or with no
    registered scope in it, has ``"scopes": []``. ``result`` is the result
    type with layouts left out (``f32[2048,16]``, ``(f32[9,16], f32[9])``):
    with the name it tells two programs' ``%fusion.12`` apart. Reads each
    program's text once (seconds for a program of 30,000 instructions); a
    program that was collected, or whose text cannot be had, is left out."""
    out: Dict[str, Dict[str, dict]] = {}
    # in the order noted (a dict keeps it); a copy: a collection may pop one
    for entry in list(_programs.values()):
        program = entry.ref()
        if program is None:
            continue
        if entry.scopes is None:
            try:
                text = program.as_text()
            except Exception:  # raydp-lint: disable=swallowed-exceptions (a backend that cannot print a program must not fail whoever asked about the others)
                continue
            entry.scopes = scopes_in_text(text)
        out[f"{entry.what}#{entry.seq}"] = entry.scopes
    return out


_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
_FLAT_TUPLE = re.compile(r"\(([^()]*)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:condition|body|to_apply|calls|true_computation|"
    r"false_computation)=%?([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME_PARTS = re.compile(r"[/()]")
# whose called computations a device trace shows as events of their own
_SHOWN_CALLERS = ("while", "conditional", "call", "async-start")
_NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element")


class _Instruction(NamedTuple):
    name: str
    result: str
    opcode: str
    chain: List[str]  # the registered scopes in its op_name
    is_root: bool
    rest: str  # the line after "=": what it calls is read from it


def _result_type(type_text: str) -> str:
    """A result type with layouts left out, by the rule the benchmark's
    trace reader applies to an event's HLO line (a nested tuple, which only
    containers have, gives "")."""
    bare = _LAYOUT.sub("", type_text).strip()
    flat = _FLAT_TUPLE.match(bare)
    if flat:
        return f"({flat.group(1)})"
    return bare.split(" ")[0].split("(")[0]


def scope_chain(op_name: str, names=None) -> List[str]:
    """The registered scopes in an ``op_name``, outermost first. A scope
    stands between slashes or inside a transform's brackets
    (``jit(f)/loss_and_grad/transpose(jvp(ssd))/mul``); the last part is the
    primitive's own name."""
    names = _scope_names if names is None else names
    chain: List[str] = []
    for part in _NAME_PARTS.split(op_name.rpartition("/")[0]):
        if part in names and part not in chain:
            chain.append(part)
    return chain


def _fusion_scopes(own: List[str], fused: List[_Instruction]):
    """(the scopes a fusion belongs to, whether it is mixed). The scopes of
    the instruction XLA itself names the fusion by, its own ``op_name``: the
    root's for an elementwise fusion, the product's for one built around a
    convolution, whatever was fused in behind it (an optimizer's update of
    a weight behind that weight's gradient product: the event is mostly the
    product, and it stays with the layer). Where the compiler left that
    name without a scope (a root of its own: a tuple of two results, a
    convert, an expanded gather named "gather"): the root's as the text has
    it, then the last fused instruction's that has any."""
    root = next((f.chain for f in fused if f.is_root), [])
    chain = own or root or next(
        (f.chain for f in reversed(fused) if f.chain), [])
    mixed = any(f.chain and f.chain[-1] not in chain
                for f in fused if f.opcode not in _NO_WORK)
    return chain, mixed


def scopes_in_text(text: str, names=None) -> Dict[str, dict]:
    """:func:`device_scopes` for one program's text (``Compiled.as_text()``);
    ``names`` stands in for the registry."""
    names = _scope_names if names is None else names
    computations: Dict[str, List[_Instruction]] = {}
    entry = current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " \t":
            head = _COMPUTATION.match(line)
            current = None
            if head:
                current = computations.setdefault(head.group(2), [])
                if head.group(1):
                    entry = head.group(2)
            continue
        found = _INSTRUCTION.match(line) if current is not None else None
        if not found:
            continue
        rest = found.group(3)
        opcode = _OPCODE.search(rest)
        op_name = _OP_NAME.search(rest)
        current.append(_Instruction(
            found.group(2),
            _result_type(rest[:opcode.start()] if opcode else rest),
            opcode.group(1) if opcode else "",
            scope_chain(op_name.group(1), names) if op_name else [],
            bool(found.group(1)), rest,
        ))

    def called(rest):
        branches = _BRANCHES.search(rest)
        listed = [] if not branches else [
            name.strip().lstrip("%") for name in branches.group(1).split(",")]
        return listed + _CALLED.findall(rest)

    def fused_by(rest, depth=0):
        # a fusion's instructions in order, a fusion inside it opened up
        found = []
        for instruction in (
                i for c in called(rest) for i in computations.get(c, ())):
            found.append(instruction)
            if instruction.opcode == "fusion" and depth < 8:
                found.extend(fused_by(instruction.rest, depth + 1))
        return found

    out: Dict[str, dict] = {}
    pending, seen = [entry] if entry else [], set()
    while pending:
        name = pending.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for instruction in computations[name]:
            chain, rest = instruction.chain, instruction.rest
            said = {"result": instruction.result, "scopes": chain}
            if instruction.opcode in _SHOWN_CALLERS:
                pending.extend(called(rest))
            elif instruction.opcode == "fusion":
                said["scopes"], mixed = _fusion_scopes(chain, fused_by(rest))
                if mixed:
                    said["mixed"] = True
            out[instruction.name] = said
    return out


# ---------------------------------------------------------------------------
# compile account (every compile of the process, by where it happened)
# ---------------------------------------------------------------------------

# what jax reports of a compile through ``jax.monitoring`` (0.9.0): three
# durations with the function's name, and the persistent cache's request and
# hit INSIDE the backend interval of the thread that asked
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_DURATION_PARTS = {
    _TRACE_EVENT: "trace_s", _LOWER_EVENT: "lower_s",
    _BACKEND_EVENT: "backend_s",
}

# a site's seconds by what jax did in them; a backend interval with a cache
# hit inside it is a load, one without is XLA compiling anew
SITE_PARTS = ("trace_s", "lower_s", "backend_s", "cache_load_s")
_SITES_KEPT = 512  # the newest sites of the process
# how long a thread remembers an interval it claimed: a later event can
# only hold what began after it did, and no trace or lowering lasts so long.
# Inside one open trace the list grows by its nested traces (thousands for
# a large model) and falls to one entry when the trace ends
_INTERVALS_KEPT_S = 1800.0
_OUTSIDE_ROWS = 64  # names kept outside any site; the 16 largest are shown
NAMED_ROWS_SHOWN = 16
_OTHER = "other"

_compile_tls = threading.local()
_listeners_installed = False
_fit_seq = itertools.count(1)
_sites: "collections.deque" = collections.deque(maxlen=_SITES_KEPT)
_outside: Dict[str, dict] = {}  # fun_name -> {seconds, programs, under}
_late: Dict[str, dict] = {}  # fun_name -> {seconds, programs, under, epoch, fit}
# fits past their first fence: fit -> (its history list, its first epoch)
_late_windows: Dict[int, Tuple[list, int]] = {}


def install_compile_listeners() -> None:
    """Register the account's two ``jax.monitoring`` listeners, once a
    process (``compile_cache.enable_compile_cache`` calls this, which every
    compiling process calls before its first compile)."""
    global _listeners_installed
    if _listeners_installed:
        return
    _listeners_installed = True
    import jax

    jax.monitoring.register_event_listener(_on_compile_event)
    jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)


def next_fit() -> int:
    """A number for a fit that begins, unique in the process: what its
    compile sites are listed under."""
    return next(_fit_seq)


def open_compile_site(what: str, fit: int) -> dict:
    """An ``estimator.compile`` span has opened on this thread: until
    :func:`close_compile_site`, what jax reports of compiles here belongs to
    the site ``what`` of fit ``fit``."""
    site = {
        "what": what, "fit": fit, "wall_s": None, "trace_s": 0.0,
        "lower_s": 0.0, "backend_s": 0.0, "cache_load_s": 0.0,
        "rest_s": None, "programs": 0, "cache_hits": 0, "cache_misses": 0,
        "_outer": getattr(_compile_tls, "site", None),
    }
    _compile_tls.site = site
    _sites.append(site)
    return site


def close_compile_site(site: dict) -> dict:
    """The span is about to end: nothing more belongs to the site. Returns
    what the span carries: the four parts, ``programs``, ``cache_hits`` and
    ``cache_misses`` (requests of the cache that it did not serve; a program
    that is neither had no cache key)."""
    _compile_tls.site = site.pop("_outer", None)
    return {key: site[key] for key in
            (*SITE_PARTS, "programs", "cache_hits", "cache_misses")}


def settle_compile_site(site: dict, wall: float) -> float:
    """The span has ended after ``wall`` seconds: the site's ``rest_s`` is
    what of them jax reported nothing of (returned), and the parts go to the
    process's counters ``estimator.compile.*``."""
    rest = max(0.0, wall - sum(site[part] for part in SITE_PARTS))
    site["wall_s"], site["rest_s"] = wall, rest
    metrics.counter("estimator.compile.trace_seconds").inc(site["trace_s"])
    metrics.counter("estimator.compile.lower_seconds").inc(site["lower_s"])
    metrics.counter("estimator.compile.backend_seconds").inc(site["backend_s"])
    metrics.counter("estimator.compile.cache_load_seconds").inc(
        site["cache_load_s"])
    metrics.counter("estimator.compile.rest_seconds").inc(rest)
    metrics.counter("estimator.compile.programs").inc(site["programs"])
    metrics.counter("estimator.compile.cache_hits").inc(site["cache_hits"])
    metrics.counter("estimator.compile.cache_misses").inc(
        site["cache_misses"])
    return rest


def open_late_window(fit: int, history: list, first_epoch: int) -> None:
    """Fit ``fit`` has fenced its first epoch: until
    :func:`close_late_window` every compile of the process is late too.
    ``history`` is the fit's live list of epoch records: its length says,
    when a late compile is named, in which epoch."""
    _late_windows[fit] = (history, first_epoch)
    # there from here on, at 0: "nothing compiled late" is a reading
    metrics.counter("estimator.compile.late_seconds")
    metrics.counter("estimator.compile.late_programs")


def close_late_window(fit: int) -> None:
    _late_windows.pop(fit, None)


def _book(table: Dict[str, dict], cap: int, nested, at: int, name: str,
          seconds: float, programs: int, **said):
    """``seconds`` and ``programs`` to the row ``name`` of a bounded table
    (past ``cap`` names: to the row ``other``; a name already met under
    another span: to a row ``name [span]``), and with them the trace
    seconds the ``nested`` intervals had booked there (their key and seconds
    at ``[at]``, ``[at + 1]``): a nested function's seconds move to the
    function whose trace held its trace, one row a program. Returns the
    row's key and what it was given."""
    for held in nested:
        row = table.get(held[at])
        if row is not None:
            row["seconds"] -= held[at + 1]
            seconds += held[at + 1]
            if row["seconds"] <= 1e-9 and not row["programs"]:
                table.pop(held[at], None)
    row = table.get(name)
    if row is not None and row.get("under") != said.get("under"):
        # a name met under another span (two ``<lambda>``s): a row of its own
        name = f"{name} [{said.get('under')}]"
        row = table.get(name)
    if row is None:
        if len(table) >= cap:
            name = _OTHER
        row = table.setdefault(name, {"seconds": 0.0, "programs": 0})
    row["seconds"] += seconds
    row["programs"] += programs
    row.update(said)
    return name, seconds


def _on_compile_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        # inside the backend interval that ends next on this thread
        _compile_tls.hit = True
    elif event == _CACHE_REQUEST_EVENT:
        site = getattr(_compile_tls, "site", None)
        if site is not None:
            site["cache_misses"] += 1  # until a hit says otherwise


def _on_compile_duration(event: str, duration: float, **kwargs) -> None:
    """One stage of one compile has ended on this thread, ``duration``
    seconds ago it began. Events fire at their END, so whatever lies inside
    this interval was claimed before it: the stage's own seconds are its
    duration less what the thread's claimed intervals hold of it (an inner
    ``jit``'s trace inside the outer's adds nothing twice; a backend compile
    inside a trace comes off the trace)."""
    part = _DURATION_PARTS.get(event)
    if part is None:
        return
    end = time.time()  # jax's own clock for these durations
    start = end - duration
    tls = _compile_tls
    claimed = getattr(tls, "claimed", None)
    if claimed is None:
        claimed = tls.claimed = collections.deque()
    name = str(kwargs.get("fun_name", "?"))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]  # lower and backend say jit(f), the trace f
    inside, first, nested = 0.0, start, []
    while claimed and claimed[-1][1] > start:
        held = claimed.pop()
        inside += held[2]
        first = min(first, held[0])
        nested.append(held)
    own = max(0.0, duration - inside)
    programs, traced = 0, part != "backend_s"
    if not traced:
        # a program: its seconds are its own, nothing moves into its row
        # or out of it
        programs, nested = 1, ()
        if getattr(tls, "hit", False):
            tls.hit, part = False, "cache_load_s"
    site = getattr(tls, "site", None)
    # the rows the interval is booked to by name, (key, seconds): outside
    # any site, and late
    outside = late = (None, 0.0)
    if site is not None:
        site[part] += own
        site["programs"] += programs
        if part == "cache_load_s":
            site["cache_hits"] += 1
            site["cache_misses"] -= 1
        under = site["what"]
    else:
        from raydp_tpu.obs import tracing

        under = tracing.current_span_name()
        outside = _book(_outside, _OUTSIDE_ROWS, nested, 4, name, own,
                        programs, under=under)
        metrics.counter("jax.compile.outside_seconds").inc(own)
        metrics.counter("jax.compile.outside_programs").inc(programs)
    if _late_windows:
        fit, (history, first_epoch) = next(iter(_late_windows.items()))
        late = _book(_late, NAMED_ROWS_SHOWN, nested, 6, name, own, programs,
                     under=under, epoch=first_epoch + len(history), fit=fit)
        metrics.counter("estimator.compile.late_seconds").inc(own)
        metrics.counter("estimator.compile.late_programs").inc(programs)
    if not traced:
        outside = late = (None, 0.0)
    claimed.append((first, end, own + inside, name, *outside, *late))
    while claimed[0][1] < end - _INTERVALS_KEPT_S:
        claimed.popleft()


def compile_account(fits=None) -> dict:
    """Every compile of the process, by where it happened::

        {"sites": [{what, fit, wall_s, trace_s, lower_s, backend_s,
                    cache_load_s, rest_s, programs, cache_hits,
                    cache_misses}],            # the estimator.compile spans
         "outside": {fun_name: {seconds, programs, under}},
         "late": [{fun_name, seconds, programs, under, epoch, fit}],
         "totals": {...}}

    ``sites``: in the order opened, the newest 512 (``wall_s`` and ``rest_s``
    None while a site is open); ``fits``: only the sites and late compiles of
    these fits (``JaxEstimator.compile_account``). ``outside``: what no site
    held, by the function's name, the 16 largest by seconds and the rest
    under ``other``; ``under`` is the innermost obs span open on the thread
    (None: none). ``late``: compiles after a fit's first fence, in a site or
    not, 16 names at most. Readable from any thread, while fits run."""
    sites = []
    for site in list(_sites):
        if fits is not None and site["fit"] not in fits:
            continue
        sites.append({k: v for k, v in site.items() if not k.startswith("_")})
    rows = sorted(
        ((name, dict(row)) for name, row in list(_outside.items())),
        key=lambda item: (item[0] == _OTHER, -item[1]["seconds"]))
    outside = dict(rows[:NAMED_ROWS_SHOWN])
    for _, row in rows[NAMED_ROWS_SHOWN:]:
        other = outside.setdefault(
            _OTHER, {"seconds": 0.0, "programs": 0, "under": None})
        other["seconds"] += row["seconds"]
        other["programs"] += row["programs"]
    late = [
        {"fun_name": name, **row} for name, row in list(_late.items())
        if fits is None or row.get("fit") in fits
    ]
    totals = {
        part: sum(site[part] for site in sites) for part in SITE_PARTS
    }
    totals.update(
        wall_s=sum(site["wall_s"] or 0.0 for site in sites),
        rest_s=sum(site["rest_s"] or 0.0 for site in sites),
        programs=sum(site["programs"] for site in sites),
        cache_hits=sum(site["cache_hits"] for site in sites),
        cache_misses=sum(site["cache_misses"] for site in sites),
        outside_s=sum(row["seconds"] for row in outside.values()),
        outside_programs=sum(row["programs"] for row in outside.values()),
        late_s=sum(row["seconds"] for row in late),
        late_programs=sum(row["programs"] for row in late),
    )
    return {"sites": sites, "outside": outside, "late": late,
            "totals": totals}


# ---------------------------------------------------------------------------
# fit attribution (the analyzer over the fit span tree)
# ---------------------------------------------------------------------------


def explain_fit(records: List[dict], top_k: int = 5) -> dict:
    """Critical-path attribution of one fit's span records (the PR 14
    analyzer over the ``estimator.fit`` tree: epoch/compile/eval children,
    epoch leaves phase-split by the step profiler's ingest/h2d/dispatch/sync
    args). ``JaxEstimator.explain_last_fit()`` is the instance-method
    spelling."""
    from raydp_tpu.obs.analysis import attribute, format_report

    report = attribute(records, root_name="estimator.fit", top_k=top_k)
    report["text"] = format_report(report)
    return report


# ---------------------------------------------------------------------------
# memory watermark plane
# ---------------------------------------------------------------------------

MEM_SAMPLE_MIN_INTERVAL_S = 1.0

_mem_lock = threading.Lock()
_last_mem_sample = 0.0
_page_size = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def _read_rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _page_size
    except (OSError, ValueError, IndexError):
        try:
            import resource

            # ru_maxrss is the PEAK (KB on linux) — an acceptable stand-in
            # where /proc is absent; the watermark gauge makes peak vs live
            # explicit either way
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:  # raydp-lint: disable=swallowed-exceptions (no rss source on this platform: the series is simply absent)
            return None


def _shm_live_bytes() -> Optional[int]:
    """Live bytes of this node's /dev/shm namespace (segments are named
    ``rtpu-<ns>-<id>``; an empty namespace owns the un-prefixed pool)."""
    ns = os.environ.get("RAYDP_TPU_SHM_NS", "")
    prefix = f"rtpu-{ns}-" if ns else "rtpu-"
    total = 0
    try:
        with os.scandir("/dev/shm") as entries:
            for entry in entries:
                if not entry.name.startswith(prefix):
                    continue
                try:
                    total += entry.stat().st_size
                except OSError:  # raydp-lint: disable=swallowed-exceptions (segment unlinked mid-scan)
                    continue
    except OSError:
        return None
    return total


def _device_live_bytes() -> Optional[int]:
    """Device live-array bytes — ONLY when jax is already imported (the
    sampler must never be the thing that drags jax into a worker)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        stats = jax.devices()[0].memory_stats() or {}
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            return int(in_use)
    except Exception:  # raydp-lint: disable=swallowed-exceptions (backend without memory stats: fall through to live_arrays)
        pass
    try:
        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # raydp-lint: disable=swallowed-exceptions (no live-array introspection on this backend either)
        return None


def _mem_pressure() -> Optional[float]:
    """Host memory pressure in [0, 1]: 1 - MemAvailable/MemTotal."""
    try:
        total = avail = None
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    total = float(line.split()[1])
                elif line.startswith("MemAvailable:"):
                    avail = float(line.split()[1])
                if total is not None and avail is not None:
                    break
        if not total or avail is None:
            return None
        return max(0.0, min(1.0, 1.0 - avail / total))
    except (OSError, ValueError, IndexError):
        return None


def sample_memory(force: bool = False) -> Optional[dict]:
    """Sample this process's memory plane into the registry (high-watermark
    gauges ``mem.{rss,shm,device}_bytes`` + ``mem.pressure``). Rides every
    obs flush tick (tracing.flush calls this first), self-throttled to
    :data:`MEM_SAMPLE_MIN_INTERVAL_S`; returns the sample dict, or None
    when throttled."""
    global _last_mem_sample
    now = time.monotonic()
    with _mem_lock:
        if not force and now - _last_mem_sample < MEM_SAMPLE_MIN_INTERVAL_S:
            return None
        _last_mem_sample = now
    sample: Dict[str, float] = {}
    rss = _read_rss_bytes()
    if rss is not None:
        sample["rss_bytes"] = float(rss)
        metrics.gauge("mem.rss_bytes").set_watermark(rss)
    shm = _shm_live_bytes()
    if shm is not None:
        sample["shm_bytes"] = float(shm)
        metrics.gauge("mem.shm_bytes").set_watermark(shm)
    device = _device_live_bytes()
    if device is not None:
        sample["device_bytes"] = float(device)
        metrics.gauge("mem.device_bytes").set_watermark(device)
    pressure = _mem_pressure()
    if pressure is not None:
        sample["pressure"] = pressure
        metrics.gauge("mem.pressure").set_watermark(pressure)
    return sample


def current_mem_pressure(window_s: float = 10.0) -> float:
    """The controllers' read of host memory pressure: the max over this
    process's recent windowed ``mem.pressure`` series with the live gauge
    as the freshness floor (the serve autoscaler and the elasticity policy
    consult this before growing a pool)."""
    sample_memory()
    live = metrics.gauge("mem.pressure").value
    try:
        from raydp_tpu.obs import timeseries as _ts

        windowed = _ts.windowed_local("mem.pressure", window_s=window_s)
        if windowed["series"] and windowed["max"] is not None:
            return max(live, windowed["max"])
    except Exception:  # raydp-lint: disable=swallowed-exceptions (the live gauge alone is a valid pressure read)
        pass
    return live
