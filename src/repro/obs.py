"""The program's own measurements: spans, counters, compile records and
stage maps, kept by one recorder per process.

* **Spans** (``span``): a name, start and end on ``time.perf_counter``, the
  enclosing span's id and a request (or batch) id, kept in memory in a
  bounded ring; the counter ``obs.spans_dropped`` counts what the ring
  drops.  Each span is also a ``jax.profiler.TraceAnnotation``, so in a
  captured profile it sits on the device trace's clock.  Spans open at
  layer boundaries only: never per element, chunk or tenant.
* **Counters** (``count``): monotone integers at the same boundaries.
* **Compile records**: one ``jax.monitoring`` listener keeps each trace,
  lowering and backend compile (or persistent-cache load) with its seconds
  and the innermost open span, which says which call compiled what.
* **Stage maps** (``dispatch``, ``stage_map``): a jitted entry point's call
  site keeps the abstract signature (shapes, dtypes, shardings, statics) of
  the call that compiled it; ``stage_map`` lowers and compiles that
  signature again, outside any timed path, and names each HLO instruction
  by the stage scope (``stage``) in its ``op_name`` metadata.  That is how
  a device trace's ``fusion.N`` gets its stage.

The recorder is process-wide (``recorder()``) because the layers that
record into it (the chunk step, the services, the scheduler) and the
readers of a benchmark run share no object that could carry one.  Nothing
here waits for the device.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time

import jax

#: the chunk step's stages (core.incremental._multi_chunk_step), in order
CHUNK_STAGES = ("chunk_sort", "score", "merge", "evict", "fold")
#: once-per-dispatch work around the chunk step's scan or tenant vmap
BOUNDARY_STAGES = ("to_keysorted", "from_keysorted", "mask_tenants")
#: the distributed two-pass program's stages (core.distributed)
TWO_PASS_STAGES = ("pass1_score", "pass1_fold", "exchange", "pass2")
STAGES = CHUNK_STAGES + BOUNDARY_STAGES + TWO_PASS_STAGES

#: jax.monitoring duration events that make up compiling a program
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float            # time.perf_counter seconds
    end: float
    id: int
    parent: int | None      # id of the enclosing span
    request: int | None     # request or batch id, inherited by children


@dataclasses.dataclass(frozen=True)
class CompileRecord:
    kind: str               # "trace", "lower" or "compile" (or cache load)
    fun: str                # the jitted function's name
    start: float            # time.perf_counter seconds
    end: float
    span: str | None        # innermost open span of the compiling thread
    request: int | None


@dataclasses.dataclass(frozen=True)
class StageMap:
    module: str             # HLO module name (a trace's module events
                            # carry it before a parenthesised id)
    stages: dict            # HLO instruction name -> stage


def stage(name: str):
    """The named scope of one of ``STAGES`` (HLO metadata only: the
    compiled code and its results do not change)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; have {STAGES}")
    return jax.named_scope(name)


_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.-]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_WRAP = re.compile(r"^(?:[\w.-]+\()+|\)+$")


def stage_of(op_name: str) -> str | None:
    """The innermost stage scope of an ``op_name`` path such as
    ``jit(f)/while/body/vmap(merge)/gather``."""
    for part in reversed(op_name.split("/")):
        name = _WRAP.sub("", part)
        if name in STAGES:
            return name
    return None


def parse_stage_map(hlo_text: str) -> StageMap:
    """The stage of each instruction of a compiled module's text: the
    stage scope in its own ``op_name``, else (a fusion whose root lost
    its metadata to a compiler pass) the most common stage of the
    computations it calls."""
    own, calls, body = {}, {}, collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            comp = line.split(" ")[0].lstrip("%") if line.endswith("{") \
                else None
            continue
        hit = _INSTR.match(line)
        if hit is None or comp is None:
            continue
        name = hit.group(1)
        body[comp].append(name)
        op = _OP_NAME.search(line)
        own[name] = stage_of(op.group(1)) if op else None
        calls[name] = _CALLS.findall(line)
    memo: dict = {}

    def of_instr(name):
        if own.get(name) is not None:
            return own[name]
        found = [of_comp(c) for c in calls.get(name, ())]
        return next((st for st in found if st is not None), None)

    def of_comp(c):
        if c not in memo:
            memo[c] = None          # a computation does not call itself
            votes = collections.Counter(of_instr(n) for n in body.get(c, ()))
            votes.pop(None, None)
            memo[c] = votes.most_common(1)[0][0] if votes else None
        return memo[c]

    stages = {n: st for n in own if (st := of_instr(n)) is not None}
    m = _MODULE.search(hlo_text)
    return StageMap(m.group(1) if m else "", stages)


def _abstract(tree):
    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                        weak_type=x.aval.weak_type)
        return x
    return jax.tree.map(leaf, tree)


def _compiled_text(lowered) -> str:
    try:
        return lowered.compile().as_text()
    except NotImplementedError:
        # an executable loaded from the persistent cache may lack its HLO:
        # compile once more without the cache (XLA names deterministically)
        from jax.experimental.compilation_cache import compilation_cache

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return lowered.compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


class Recorder:
    """Spans, counters, compile records and entry signatures of one
    process (or of one test)."""

    def __init__(self, capacity: int = 4096):
        self.spans: collections.deque = collections.deque(maxlen=capacity)
        self.compiles: collections.deque = collections.deque(maxlen=capacity)
        self.counters: collections.Counter = collections.Counter()
        #: entry -> (jitted function, abstract args, abstract kwargs)
        self.signatures: dict = {}
        #: entry -> StageMap of its signature, filled by ``stage_map``
        self.stage_maps: dict = {}
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._listener = None

    # -- spans and counters ----------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, ring: collections.deque, item, dropped: str) -> None:
        with self._lock:
            if len(ring) == ring.maxlen:
                self.counters[dropped] += 1
            ring.append(item)

    @contextlib.contextmanager
    def span(self, name: str, request: int | None = None):
        """Time the block as a span; a child inherits its parent's request
        id unless given its own."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        if request is None and parent is not None:
            request = parent[2]
        sid = next(self._ids)
        stack.append((name, sid, request))
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            end = time.perf_counter()
            stack.pop()
            self._keep(self.spans,
                       Span(name, start, end, sid,
                            parent[1] if parent else None, request),
                       "obs.spans_dropped")

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += int(n)

    # -- compile records -------------------------------------------------

    def listen(self) -> None:
        """Start recording compiles (idempotent)."""
        if self._listener is not None:
            return

        def on_duration(event, duration, **kw):
            kind = COMPILE_EVENTS.get(event)
            if kind is None:
                return
            end = time.perf_counter()
            stack = self._stack()
            name, _, request = stack[-1] if stack else (None, None, None)
            rec = CompileRecord(kind, str(kw.get("fun_name", "")),
                                end - duration, end, name, request)
            with self._lock:
                # tracing a function traces the jitted functions it calls
                # first: the outer record replaces the inner ones it covers
                while (self.compiles and self.compiles[-1].start >= rec.start
                       and self.compiles[-1].end <= rec.end):
                    self.compiles.pop()
            self._keep(self.compiles, rec, "obs.compiles_dropped")

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._listener = on_duration

    def close(self) -> None:
        """Stop recording compiles."""
        if self._listener is not None:
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None

    # -- stage maps ------------------------------------------------------

    def dispatch(self, entry: str, fn, *args, **kwargs):
        """Call the jitted ``fn``; when the call compiled (its cache grew),
        keep its abstract signature as ``entry``'s."""
        before = fn._cache_size()
        out = fn(*args, **kwargs)
        if fn._cache_size() != before:
            # donated inputs are deleted by now but keep shape and sharding
            sig = (fn, _abstract(args), _abstract(kwargs))
            with self._lock:
                self.signatures[entry] = sig
                self.stage_maps.pop(entry, None)
        return out

    def stage_map(self, entry: str) -> StageMap | None:
        """The stage map of the call that last compiled ``entry``; None
        when it never compiled in this process.  Compiles (or loads) the
        program once more: call it outside any timed path."""
        if entry not in self.stage_maps:
            sig = self.signatures.get(entry)
            if sig is None:
                return None
            fn, args, kwargs = sig
            self.stage_maps[entry] = parse_stage_map(
                _compiled_text(fn.lower(*args, **kwargs)))
        return self.stage_maps[entry]


_recorder: Recorder | None = None


def recorder() -> Recorder:
    """The process's recorder, made (and listening) on first use."""
    global _recorder
    if _recorder is None:
        _recorder = Recorder()
        _recorder.listen()
    return _recorder


def span(name: str, request: int | None = None):
    return recorder().span(name, request)


def count(name: str, n: int = 1) -> None:
    recorder().count(name, n)


def dispatch(entry: str, fn, *args, **kwargs):
    return recorder().dispatch(entry, fn, *args, **kwargs)


def stage_map(entry: str) -> StageMap | None:
    return recorder().stage_map(entry)
