"""The two-phase execution engine (paper §III-D, §V).

Compression is split into:

  * **resolve** — ``resolve(plan, streams, ctx) -> ResolvedPlan``: selector
    expansion.  Walks the plan in topological order, expanding selectors
    recursively, and emits a linear codec-only program.  Resolution is
    memoized on ``(plan, stream metas, level, format_version)`` so a deployed
    compressor pays for selector trials once per stream shape, not once per
    ``compress()`` call.
  * **execute** — ``execute(resolved, streams, backend=...) -> frame``: runs
    the codec encoders over concrete data.  Encoders dispatch per *backend*:
    ``host`` is the numpy codec suite; ``device`` routes numeric transform
    nodes through the jit'd Pallas wrappers in ``repro.kernels.ops``.  Both
    backends execute the same resolved steps and write the same frame, byte
    for byte.

``compress()`` composes the two and optionally chunks large inputs
(``chunk_bytes=N``) into independently compressed pieces executed on a thread
pool (numpy/zlib/JAX release the GIL) and stored in a multi-chunk container
frame (``wire.py``, format v4+).

Sessions (streaming engine)
---------------------------
:class:`CompressorSession` / :class:`DecompressorSession` are the long-lived
form of those one-shot calls: a session owns the resolved plan, the coder-table
scratch, the backend choice, and a persistent thread pool, so a service pays
for spin-up once, not per request.  The chunked path pipelines *split →
parallel encode → in-order incremental write* behind a bounded in-flight
window (peak memory ≈ window × chunk_bytes — never the input size — when fed
from a lazy chunk source such as ``repro.core.stream_io``).  The module-level
``compress()``/``decompress()`` are thin wrappers over a throwaway session;
their wire output is unchanged, byte for byte.

Decompression is purely procedural and backend-free: parse the frame, run
codec decoders in reverse topological order.  No parameters, no selectors, no
user code — any frame any graph ever produced decodes with this one function,
including both single- and multi-chunk frames.
"""
from __future__ import annotations

import dataclasses
import io
import os
import threading
import time
from collections import Counter, OrderedDict, deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor
from concurrent.futures import wait as _futures_wait
from dataclasses import dataclass, field
from typing import (
    BinaryIO,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import numpy as np

from repro.device import span

from . import wire
from .codec import (
    available_backends,
    get_codec,
    get_codec_by_id,
    run_encode_via,
)
from .graph import KIND_CODEC, KIND_SELECTOR, Plan
from .message import Stream, SType, serial
from .selector import get_selector
from .versioning import (
    CONTAINER_MIN_VERSION,
    CURRENT_FORMAT_VERSION,
    check_compress_version,
    check_decode_version,
)

__all__ = [
    "CompressionCtx",
    "ExecScratch",
    "ResolvedNode",
    "ResolvedStep",
    "ResolvedPlan",
    "StreamMeta",
    "stream_meta",
    "resolve",
    "execute",
    "resolve_cache_info",
    "resolve_cache_clear",
    "set_resolve_check",
    "compress",
    "decompress",
    "decompress_bytes",
    "Compressor",
    "CompressorSession",
    "DecompressorSession",
    "SessionPool",
]

@dataclass
class CompressionCtx:
    """Knobs visible to selectors during expansion."""

    format_version: int = CURRENT_FORMAT_VERSION
    level: int = 5  # 1 (fastest) .. 9 (smallest); selectors may consult this
    extras: dict = field(default_factory=dict)


class ExecScratch:
    """Per-``execute()`` scratch state threaded through codec invocations.

    Today it scopes the entropy coder-table cache (``repro.codecs
    .coder_cache``): one compression call — including every chunk the
    ``chunk_bytes=N`` thread pool fans out — shares a single read-only table
    namespace, so identical Huffman/FSE tables are built once, not once per
    chunk.  Chunk workers receive the *same* ``ExecScratch``; the cache it
    wraps is lock-guarded and its values immutable, which is what makes the
    sharing thread-safe.
    """

    def __init__(self, table_cache_size: int = 256):
        from repro.codecs.coder_cache import CoderCache  # lazy: no core cycle

        self.coder_cache = CoderCache(maxsize=table_cache_size)

    def activate(self):
        """Context manager making this scratch current for codec calls."""
        from repro.codecs.coder_cache import scoped

        return scoped(self.coder_cache)

    def table_cache_info(self) -> dict:
        return self.coder_cache.info()


@dataclass(frozen=True)
class ResolvedNode:
    """One executed codec as recorded on the wire (headers are per-call)."""

    codec_id: int
    inputs: Tuple[int, ...]
    n_out: int
    header: bytes


# ----------------------------------------------------------- resolved plans
@dataclass(frozen=True)
class StreamMeta:
    """The shape of a stream, for resolve-cache keying (not its contents)."""

    stype: SType
    width: int
    size_bucket: int  # floor(log2(n_elts))+1 — selector choices track scale


def stream_meta(s: Stream) -> StreamMeta:
    return StreamMeta(s.stype, s.width, int(s.n_elts).bit_length())


@dataclass(frozen=True)
class ResolvedStep:
    """One codec invocation in a resolved program.

    Edge ids are *resolved-plan* ids: inputs ``0..n_inputs-1`` are the graph
    inputs, each step's outputs take the next consecutive ids.  Each step
    runs as one wire node, so these are also the frame's edge ids, unless a
    ``fused_delta_bitpack`` step refuses its data (:class:`_Executor`).
    """

    name: str
    codec_id: int
    inputs: Tuple[int, ...]
    n_out: int
    params: tuple = ()  # frozen dict items (graph.py _freeze format)

    def param_dict(self) -> dict:
        from .graph import _thaw

        return _thaw(self.params) if self.params else {}


@dataclass(frozen=True)
class ResolvedPlan:
    """A selector-free compression program: the cacheable resolve artifact."""

    n_inputs: int
    steps: Tuple[ResolvedStep, ...]
    format_version: int
    level: int
    name: str = ""

    @property
    def n_edges(self) -> int:
        return self.n_inputs + sum(s.n_out for s in self.steps)

    def codec_names(self) -> List[str]:
        return [s.name for s in self.steps]


# ------------------------------------------------------------- resolve phase
class _Resolver:
    """Expands selectors by walking the plan over concrete streams.

    Intermediate streams are materialized with host encoders because nested
    selectors sample their *actual* inputs (trial compression).  The encoded
    bytes are discarded — only the step list survives, which is what makes
    the result reusable across calls.
    """

    def __init__(self, ctx: CompressionCtx):
        self.ctx = ctx
        self.edges: List[Stream] = []
        self.consumed: List[bool] = []
        self.steps: List[ResolvedStep] = []

    def new_edge(self, s: Stream) -> int:
        self.edges.append(s)
        self.consumed.append(False)
        return len(self.edges) - 1

    def consume(self, e: int) -> Stream:
        if self.consumed[e]:
            raise AssertionError(f"edge {e} consumed twice at resolution")
        self.consumed[e] = True
        return self.edges[e]

    def run_plan(self, plan: Plan, input_edge_ids: Sequence[int], depth: int = 0):
        if depth > 64:
            raise RecursionError("selector expansion too deep (cycle?)")
        if len(input_edge_ids) != plan.n_inputs:
            raise ValueError(
                f"plan {plan.name!r} wants {plan.n_inputs} inputs,"
                f" got {len(input_edge_ids)}"
            )
        edge_of: Dict[int, int] = {i: eid for i, eid in enumerate(input_edge_ids)}
        next_plan_edge = plan.n_inputs
        for node in plan.nodes:
            in_ids = [edge_of[e] for e in node.inputs]
            if node.kind == KIND_CODEC:
                spec = _checked_codec(node.name, self.ctx.format_version)
                ins = [self.consume(e) for e in in_ids]
                outs, _header = spec.run_encode(ins, node.param_dict())
                if len(outs) != node.n_out:
                    raise AssertionError(
                        f"codec {node.name}: declared n_out={node.n_out},"
                        f" produced {len(outs)}"
                    )
                out_ids = [self.new_edge(o) for o in outs]
                self.steps.append(
                    ResolvedStep(
                        node.name, spec.codec_id, tuple(in_ids), node.n_out, node.params
                    )
                )
                for k, oid in enumerate(out_ids):
                    edge_of[next_plan_edge + k] = oid
                next_plan_edge += node.n_out
            else:  # selector: expand recursively
                sel = get_selector(node.name)
                ins = [self.edges[e] for e in in_ids]  # peek, not consume
                subplan = sel.fn(ins, node.param_dict(), self.ctx).validate()
                self.run_plan(subplan, in_ids, depth + 1)


def _checked_codec(name: str, format_version: int):
    spec = get_codec(name)
    if spec.min_version > format_version:
        raise ValueError(
            f"codec {name!r} requires format version"
            f" >= {spec.min_version}, compressing at {format_version}"
        )
    return spec


def _flatten(plan: Plan, ctx: CompressionCtx) -> Tuple[ResolvedStep, ...]:
    """Selector-free plans resolve without touching any data."""
    steps = []
    for node in plan.nodes:
        spec = _checked_codec(node.name, ctx.format_version)
        steps.append(
            ResolvedStep(node.name, spec.codec_id, node.inputs, node.n_out, node.params)
        )
    return tuple(steps)


# The memo: (plan, input metas, level, format_version) -> ResolvedPlan.  LRU
# so long-running services with many stream shapes stay bounded.
_CACHE_MAX = 512
_cache: "OrderedDict[tuple, ResolvedPlan]" = OrderedDict()
_cache_lock = threading.Lock()
_cache_stats = {"hits": 0, "misses": 0, "miss_s": 0.0}
# nesting depth of resolves on this thread: a selector's trial compressions
# resolve their candidate plans inside the outer resolve, and ``miss_s``
# counts the outer one's time only
_resolving = threading.local()


def resolve_cache_info() -> dict:
    """Cache traffic; ``miss_s`` is the seconds spent resolving what the
    cache did not answer (outermost resolves only)."""
    with _cache_lock:
        return {
            "hits": _cache_stats["hits"],
            "misses": _cache_stats["misses"],
            "miss_s": _cache_stats["miss_s"],
            "size": len(_cache),
            "maxsize": _CACHE_MAX,
        }


def resolve_cache_clear() -> None:
    with _cache_lock:
        _cache.clear()
        _cache_stats["hits"] = 0
        _cache_stats["misses"] = 0
        _cache_stats["miss_s"] = 0.0


# Opt-in debug assert: type-check every plan entering resolve() against the
# concrete input types (repro.analysis).  Off by default — the static check
# belongs at the registration/training boundary, not the per-call hot path.
_RESOLVE_CHECK = os.environ.get("REPRO_RESOLVE_CHECK", "") not in ("", "0")


def set_resolve_check(enabled: bool) -> None:
    """Toggle the ``REPRO_RESOLVE_CHECK`` debug assert programmatically."""
    global _RESOLVE_CHECK
    _RESOLVE_CHECK = bool(enabled)


def _debug_check_plan(plan: Plan, metas, ctx) -> None:
    from repro.analysis import PlanTypeError, check_plan  # lazy: no cycle

    report = check_plan(
        plan,
        format_version=ctx.format_version,
        input_atoms=[(int(m.stype), int(m.width)) for m in metas],
    )
    if not report.ok:
        raise PlanTypeError(
            f"resolve check: plan {plan.name!r} is ill-typed for these"
            f" inputs: {'; '.join(str(d) for d in report.errors)}",
            report.errors,
        )


def _engine_after_fork() -> None:
    """Re-arm the module-level cache lock in a forked child.

    The service plane pre-forks session-worker processes (and forks again to
    replace a crashed one) while the parent may be resolving concurrently; a
    lock captured mid-acquire would deadlock the child's first resolve.  The
    memoized entries themselves are immutable and carry over — a worker forked
    from a warmed parent starts with a hot resolve cache.
    """
    global _cache_lock
    _cache_lock = threading.Lock()


if hasattr(os, "register_at_fork"):  # pragma: no branch - POSIX in CI
    os.register_at_fork(after_in_child=_engine_after_fork)


def _as_streams(inputs) -> List[Stream]:
    if isinstance(inputs, (bytes, bytearray, memoryview)):
        return [serial(inputs)]
    if isinstance(inputs, Stream):
        return [inputs]
    return [s for s in inputs]


def resolve(
    plan: Plan,
    inputs: Union[Stream, bytes, Sequence[Stream], Sequence[StreamMeta]],
    ctx: Optional[CompressionCtx] = None,
    *,
    use_cache: bool = True,
) -> ResolvedPlan:
    """Phase 1: expand selectors once -> a cached, inspectable ResolvedPlan.

    ``inputs`` may be concrete streams or bare :class:`StreamMeta` values;
    metas suffice only for selector-free plans (dynamic plans need real data
    to run trial compressions on).
    """
    resolved, _was_hit = _resolve_impl(plan, inputs, ctx, use_cache=use_cache)
    return resolved


def _resolve_impl(
    plan: Plan,
    inputs,
    ctx: Optional[CompressionCtx],
    *,
    use_cache: bool,
) -> Tuple[ResolvedPlan, bool]:
    """resolve() plus whether the result came from the cache (for fallback)."""
    ctx = ctx or CompressionCtx()
    check_compress_version(ctx.format_version)
    items = _as_streams(inputs) if not _all_metas(inputs) else list(inputs)
    metas_only = _all_metas(items)
    if metas_only:
        metas = tuple(items)
    else:
        items = [s.validate() for s in items]
        metas = tuple(stream_meta(s) for s in items)
    if len(metas) != plan.n_inputs:
        raise ValueError(
            f"plan {plan.name!r} wants {plan.n_inputs} inputs, got {len(metas)}"
        )

    key = (plan, metas, ctx.level, ctx.format_version)
    if use_cache:
        with _cache_lock:
            hit = _cache.get(key)
            if hit is not None:
                _cache.move_to_end(key)
                _cache_stats["hits"] += 1
                return hit, True
            _cache_stats["misses"] += 1

    depth = getattr(_resolving, "depth", 0)
    _resolving.depth = depth + 1
    t0 = time.perf_counter()
    try:
        with span("ozl.resolve"):
            resolved = _resolve_uncached(plan, items, metas, metas_only, ctx)
    finally:
        _resolving.depth = depth
        if not depth:
            spent = time.perf_counter() - t0
            with _cache_lock:
                _cache_stats["miss_s"] += spent
    if use_cache:
        with _cache_lock:
            _cache[key] = resolved
            while len(_cache) > _CACHE_MAX:
                _cache.popitem(last=False)
    return resolved, False


def _resolve_uncached(plan: Plan, items, metas, metas_only: bool,
                      ctx: CompressionCtx) -> ResolvedPlan:
    plan.validate()
    if _RESOLVE_CHECK:
        _debug_check_plan(plan, metas, ctx)
    if plan.is_resolved:
        steps = _flatten(plan, ctx)
    else:
        if metas_only:
            raise ValueError(
                "resolving a plan with selectors requires concrete streams,"
                " not StreamMeta"
            )
        r = _Resolver(ctx)
        in_ids = [r.new_edge(s) for s in items]
        r.run_plan(plan, in_ids)
        steps = tuple(r.steps)
    return ResolvedPlan(len(metas), steps, ctx.format_version, ctx.level, plan.name)


def _all_metas(inputs) -> bool:
    return (
        isinstance(inputs, (list, tuple))
        and len(inputs) > 0
        and all(isinstance(x, StreamMeta) for x in inputs)
    )


# ------------------------------------------------------------- execute phase
def _lower_refused_fused(
    steps: Sequence[ResolvedStep], out_edge: int
) -> List[ResolvedStep]:
    """``steps[0]`` is a ``fused_delta_bitpack`` step whose lossless
    precondition failed on this data: run it as ``delta`` -> ``bitpack``
    instead, on every backend alike.  Its output edge ``out_edge`` becomes the
    interior delta edge, so every later edge id shifts up by one."""
    fused = steps[0]
    bits = int(fused.param_dict().get("bits", 0))
    return [
        ResolvedStep("delta", get_codec("delta").codec_id, fused.inputs, 1),
        ResolvedStep(
            "bitpack", get_codec("bitpack").codec_id, (out_edge,), 1,
            (("bits", bits),) if bits else (),
        ),
    ] + [
        dataclasses.replace(s, inputs=tuple(e + (e >= out_edge) for e in s.inputs))
        for s in steps[1:]
    ]


class _Executor:
    """Runs a ResolvedPlan over concrete streams with backend dispatch.

    Every resolved step runs as exactly one wire node whatever the backend,
    so runtime edge ids are the resolved edge ids: graph inputs first, then
    each node's outputs in execution order.  The one data-dependent rewrite
    is backend-independent: a ``fused_delta_bitpack`` step that refuses its
    data runs as ``delta`` -> ``bitpack`` (:func:`_lower_refused_fused`).

    ``trace`` (optional) collects one ``(codec_name, input_bytes)`` pair per
    executed codec, in execution order — the raw material for the trainer's
    deterministic cost model (the counts are a pure function of plan + data,
    unlike wall-clock timings).  ``routes`` (optional) counts executed nodes
    by ``(encoded_by_backend, codec_name)``: a node the selected backend
    declined counts under ``"host"``.
    """

    def __init__(
        self,
        resolved: ResolvedPlan,
        streams: Sequence[Stream],
        backend: str,
        trace: Optional[List[Tuple[str, int]]] = None,
        routes: Optional[Counter] = None,
    ):
        self.resolved = resolved
        self.backend = backend
        self.trace = trace
        self.routes = routes
        self.edges: List[Stream] = list(streams)
        self.consumed: List[bool] = [False] * len(self.edges)
        self.nodes: List[ResolvedNode] = []

    def _consume(self, e: int) -> Stream:
        if self.consumed[e]:
            raise AssertionError(f"edge {e} consumed twice at runtime")
        self.consumed[e] = True
        return self.edges[e]

    def run(self) -> bytes:
        steps = list(self.resolved.steps)
        i = 0
        while i < len(steps):
            step = steps[i]
            spec = _checked_codec(step.name, self.resolved.format_version)
            ins = [self._consume(e) for e in step.inputs]
            try:
                outs, header, by = run_encode_via(
                    spec, self.backend, ins, step.param_dict()
                )
            except ValueError:
                if step.name != "fused_delta_bitpack":
                    raise
                for e in step.inputs:
                    self.consumed[e] = False
                steps[i:] = _lower_refused_fused(steps[i:], len(self.edges))
                continue
            if len(outs) != step.n_out:
                raise AssertionError(
                    f"codec {step.name}: resolved n_out={step.n_out},"
                    f" produced {len(outs)}"
                )
            if self.trace is not None:
                self.trace.append((step.name, sum(s.nbytes for s in ins)))
            if self.routes is not None:
                self.routes[(by, step.name)] += 1
            self.edges.extend(outs)
            self.consumed.extend([False] * len(outs))
            self.nodes.append(ResolvedNode(spec.codec_id, step.inputs, len(outs), header))
            i += 1
        stored = [
            (eid, self.edges[eid])
            for eid in range(len(self.edges))
            if not self.consumed[eid]
        ]
        return wire.write_frame(
            self.resolved.format_version, self.resolved.n_inputs, self.nodes, stored
        )


def execute(
    resolved: ResolvedPlan,
    inputs: Union[Stream, bytes, Sequence[Stream]],
    *,
    backend: str = "host",
    scratch: Optional[ExecScratch] = None,
    trace: Optional[List[Tuple[str, int]]] = None,
    routes: Optional[Counter] = None,
) -> bytes:
    """Phase 2: run a resolved program over concrete streams -> wire frame.

    ``scratch`` scopes per-call coder-table caching; the chunked
    ``compress()`` path passes one shared scratch to every pool worker so
    read-only tables are built once.
    ``trace`` (a caller-owned list) collects ``(codec_name, input_bytes)`` per
    executed step and ``routes`` (a caller-owned Counter) the backend that
    encoded each node — see :class:`_Executor`.
    """
    streams = [s.validate() for s in _as_streams(inputs)]
    if len(streams) != resolved.n_inputs:
        raise ValueError(
            f"resolved plan wants {resolved.n_inputs} inputs, got {len(streams)}"
        )
    if backend not in available_backends():
        raise ValueError(
            f"unknown backend {backend!r}; available: {available_backends()}"
        )
    if scratch is None:
        return _Executor(resolved, streams, backend, trace, routes).run()
    with scratch.activate():
        return _Executor(resolved, streams, backend, trace, routes).run()


# ------------------------------------------------------------------ chunking
def _split_chunks(s: Stream, chunk_bytes: int) -> List[Stream]:
    """Element-aligned split; every chunk holds at least one element.

    STRING streams pack greedily: a chunk takes whole strings while its byte
    total stays <= ``chunk_bytes`` (the first string is always taken, however
    large).  The boundaries come from one int64 cumsum over ``lengths`` plus a
    binary search per emitted chunk — O(n + chunks·log n), replacing the
    per-string Python loop.
    """
    if chunk_bytes < 1:
        raise ValueError("chunk_bytes must be >= 1")
    if s.stype == SType.STRING:
        lens = s.lengths if s.lengths is not None else np.zeros(0, np.uint32)
        if lens.size == 0:
            return [s]
        pre = np.zeros(lens.size + 1, np.int64)  # exclusive byte offsets
        np.cumsum(lens, dtype=np.int64, out=pre[1:])
        out: List[Stream] = []
        i = 0
        while i < lens.size:
            j = int(np.searchsorted(pre, pre[i] + chunk_bytes, side="right")) - 1
            j = max(j, i + 1)
            out.append(
                Stream(s.data[int(pre[i]) : int(pre[j])], SType.STRING, 1, lens[i:j])
            )
            i = j
        return out
    elt_bytes = s.width if s.stype in (SType.NUMERIC, SType.STRUCT) else 1
    per = max(1, chunk_bytes // elt_bytes)
    n = s.n_elts
    if n <= per:
        return [s]
    datum_per_elt = s.width if s.stype == SType.STRUCT else 1
    return [
        Stream(s.data[i * datum_per_elt : (i + per) * datum_per_elt], s.stype, s.width)
        for i in range(0, n, per)
    ]


def _concat_decoded(parts: List[Stream]) -> Stream:
    s0 = parts[0]
    if any(p.stype != s0.stype or p.width != s0.width for p in parts):
        raise wire.FrameError("container chunks disagree on stream type")
    if s0.stype == SType.STRING:
        data = np.concatenate([p.data for p in parts])
        lengths = np.concatenate(
            [
                p.lengths if p.lengths is not None else np.zeros(0, np.uint32)
                for p in parts
            ]
        ).astype(np.uint32)
        return Stream(data, SType.STRING, 1, lengths).validate()
    arrays = [
        p.as_unsigned().data if p.stype == SType.NUMERIC else p.data for p in parts
    ]
    return Stream(np.concatenate(arrays), s0.stype, s0.width).validate()


# ------------------------------------------------------------------ sessions
_DRAW_END = object()  # sentinel: the chunk source is exhausted


class _SessionBase:
    """Shared pool/scratch plumbing for the two session classes."""

    def __init__(
        self,
        n_workers: Optional[int],
        window: Optional[int],
        table_cache_size: int,
        pool_name: str,
        scratch: Optional[ExecScratch] = None,
    ):
        self.n_workers = n_workers
        # a caller-provided scratch lets many sessions share one coder-table
        # cache (the trainer holds hundreds of tiny per-genome sessions)
        self.scratch = scratch if scratch is not None else ExecScratch(table_cache_size)
        self._window = window
        self._pool: Optional[ThreadPoolExecutor] = None
        self._draw_pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        self._pool_name = pool_name
        self._stats_lock = threading.Lock()
        self.stats: Dict[str, float] = {
            "calls": 0,
            "chunks": 0,
            "bytes_in": 0,
            "bytes_out": 0,
            "max_inflight": 0,
            # double-buffer accounting: a *hit* is a source draw (split /
            # read / host->device transfer) that finished entirely in the
            # shadow of in-flight encodes; the _s counters are main-loop
            # seconds blocked on each pipeline stage
            "prefetch_hits": 0,
            "prefetch_misses": 0,
            "draw_wait_s": 0.0,
            "encode_wait_s": 0.0,
        }

    def _bump(self, **deltas: int) -> None:
        """Lock-guarded counter updates (sessions may be shared by threads)."""
        with self._stats_lock:
            for k, v in deltas.items():
                self.stats[k] += v

    def _pool_get(self) -> ThreadPoolExecutor:
        """The persistent executor, created on first chunked call."""
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.n_workers or len(os.sched_getaffinity(0)),
                    thread_name_prefix=self._pool_name,
                )
            return self._pool

    def _draw_pool_get(self) -> ThreadPoolExecutor:
        """Dedicated single thread for source draws: the double buffer's host
        stage must not queue behind encodes on the shared pool, or a busy
        window would serialize exactly the work prefetch exists to hide."""
        with self._pool_lock:
            if self._draw_pool is None:
                self._draw_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix=self._pool_name + "-draw"
                )
            return self._draw_pool

    @property
    def window(self) -> int:
        """Max chunks in flight: bounds peak memory at ~window × chunk size."""
        if self._window:
            return max(1, self._window)
        return 2 * (self.n_workers or len(os.sched_getaffinity(0)))

    def _window_map(
        self, fn: Callable, items: Iterable, head: Optional[list] = None
    ) -> Iterator:
        """Map ``fn`` over ``items`` on the pool, yielding results *in order*
        while keeping at most ``self.window`` tasks (and their inputs/outputs)
        alive.  ``head`` prepends already-drawn items without re-consuming the
        iterator.

        Double-buffered: the next item is drawn from the source on a
        dedicated thread while encodes are in flight, so chunk N's encode
        overlaps chunk N+1's host stage (split, file read, host->device
        transfer for a lazy source).  At most one draw is in flight,
        preserving the source's single-consumer contract; the
        prefetch_hits / draw_wait_s counters in :attr:`stats` report how much
        of the host stage the overlap actually hid."""
        pool = self._pool_get()
        window = self.window
        it = iter(items)
        pending: "deque" = deque(pool.submit(fn, x) for x in (head or []))
        drawer = self._draw_pool_get()
        draw = drawer.submit(next, it, _DRAW_END)
        exhausted = False
        try:
            while pending or not exhausted:
                while not exhausted and len(pending) < window:
                    hidden = bool(pending) and draw.done()
                    t0 = time.perf_counter()
                    item = draw.result()
                    dt = time.perf_counter() - t0
                    if item is _DRAW_END:
                        exhausted = True
                        draw = None
                        break
                    pending.append(pool.submit(fn, item))
                    draw = drawer.submit(next, it, _DRAW_END)
                    with self._stats_lock:
                        key = "prefetch_hits" if hidden else "prefetch_misses"
                        self.stats[key] += 1
                        self.stats["draw_wait_s"] += dt
                if not pending:
                    break
                with self._stats_lock:
                    if len(pending) > self.stats["max_inflight"]:
                        self.stats["max_inflight"] = len(pending)
                t0 = time.perf_counter()
                # wait on the oldest encode AND the in-flight draw: a source
                # that dies drawing chunk N+1 fails the call as soon as the
                # draw thread reports it, instead of hiding behind a full
                # window of slow encodes
                while True:
                    waiters = [pending[0]]
                    if draw is not None and not draw.done():
                        waiters.append(draw)
                    _futures_wait(waiters, return_when=FIRST_COMPLETED)
                    if (
                        draw is not None
                        and draw.done()
                        and draw.exception() is not None
                    ):
                        draw.result()  # raises the source's error promptly
                    if pending[0].done():
                        break
                result = pending.popleft().result()
                with self._stats_lock:
                    self.stats["encode_wait_s"] += time.perf_counter() - t0
                yield result
        finally:
            for fut in pending:
                fut.cancel()
            if draw is not None:
                draw.cancel()

    def close(self) -> None:
        """Release the pool.  The session object stays usable (a new pool is
        created on demand), so throwaway wrapper usage is cheap and idempotent."""
        with self._pool_lock:
            pool, self._pool = self._pool, None
            draw_pool, self._draw_pool = self._draw_pool, None
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)
        if draw_pool is not None:
            draw_pool.shutdown(wait=True, cancel_futures=True)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:
            pass


class CompressorSession(_SessionBase):
    """A reusable, streaming compression session (one plan, many inputs).

    Owns everything a ``compress()`` call would otherwise rebuild: the
    resolve-cache handle for its plan, a coder-table :class:`ExecScratch`
    shared by every chunk it ever encodes, the backend choice, and a
    persistent thread pool.  The chunked path pipelines *split → parallel
    encode → in-order incremental write* behind a bounded in-flight window, so
    feeding it a lazy chunk iterator (``repro.core.stream_io``) compresses
    arbitrarily large inputs with peak memory ≈ ``window × chunk_bytes``.
    The window is double-buffered: chunk N+1's host stage — split, file
    read, host->device transfer — is drawn on its own thread while chunk N
    encodes, and ``stats["prefetch_hits"]`` / ``stats["draw_wait_s"]`` /
    ``stats["encode_wait_s"]`` report how much of it the overlap hid.  Knobs:
    ``window`` bounds chunks in flight, ``n_workers`` sizes the pool.

    Output is byte-identical to the module-level ``compress()`` with the same
    arguments — sessions change *when* work happens, never the wire format.
    Thread-safe for concurrent ``compress()`` calls (the scratch cache and
    resolve cache are lock-guarded and value-immutable).
    """

    def __init__(
        self,
        plan: Plan,
        *,
        ctx: Optional[CompressionCtx] = None,
        backend: str = "host",
        chunk_bytes: Optional[int] = None,
        n_workers: Optional[int] = None,
        window: Optional[int] = None,
        use_resolve_cache: bool = True,
        table_cache_size: int = 256,
        scratch: Optional[ExecScratch] = None,
        failover: Optional[object] = None,
    ):
        super().__init__(n_workers, window, table_cache_size, "ozl-enc", scratch)
        self.plan = plan.validate()
        self.ctx = ctx or CompressionCtx()
        check_compress_version(self.ctx.format_version)
        if backend not in available_backends():
            raise ValueError(
                f"unknown backend {backend!r}; available: {available_backends()}"
            )
        self.backend = backend
        self.chunk_bytes = chunk_bytes
        self.use_resolve_cache = use_resolve_cache
        # duck-typed backend-health object (quarantined / record_failure /
        # record_success — e.g. repro.reliability.BackendHealth).  With one
        # installed, a chunk whose non-host backend raises is transparently
        # re-executed on host (bit-identical frames by the backend-conformance
        # guarantee) and the failure recorded; a quarantined backend is
        # skipped outright.  None (the default) keeps errors fatal.
        self.failover = failover
        # executed nodes by the backend that encoded them -> codec -> count
        # ("host" holds nodes the session's backend declined or failed over)
        self.stats["nodes"] = {}

    # ------------------------------------------------------------ one-shot
    def compress(
        self,
        inputs: Union[Stream, bytes, Sequence[Stream]],
        *,
        chunk_bytes: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> bytes:
        """Compress to an in-memory frame (chunked -> container record).

        ``chunk_bytes`` overrides the session default; pass 0 to force an
        unchunked frame from a chunking-enabled session.
        """
        cb = self.chunk_bytes if chunk_bytes is None else chunk_bytes
        streams = [s.validate() for s in _as_streams(inputs)]
        self._bump(calls=1, bytes_in=sum(s.nbytes for s in streams))
        if cb:
            if len(streams) != 1:
                raise ValueError("chunked compression supports exactly one input")
            if self.ctx.format_version < CONTAINER_MIN_VERSION:
                raise ValueError(
                    f"chunk_bytes requires format version >= {CONTAINER_MIN_VERSION}"
                    f" (compressing at {self.ctx.format_version})"
                )
            chunks = _split_chunks(streams[0], cb)
            if len(chunks) > 1:
                buf = io.BytesIO()
                self.compress_chunks(chunks, buf, n_chunks=len(chunks), backend=backend)
                frame = buf.getvalue()
                self._bump(bytes_out=len(frame))
                return frame
        frame = self._compress_single(streams, backend or self.backend)
        self._bump(bytes_out=len(frame))
        return frame

    def _execute(
        self,
        resolved: ResolvedPlan,
        streams: List[Stream],
        backend: str,
        trace: Optional[List[Tuple[str, int]]] = None,
    ) -> bytes:
        """``execute()`` with backend-health failover to host.

        A quarantined backend is skipped before paying for the failure; an
        unquarantined one that raises is retried on host with the *same*
        resolution — only if host then succeeds is the error charged to the
        backend (a data-dependent resolve failure fails on host too and
        propagates to the caller's fresh-resolve retry, never poisoning the
        backend's health record).
        """
        fo = self.failover
        if backend != "host" and fo is not None and fo.quarantined(backend):
            backend = "host"
        routes: Counter = Counter()
        try:
            out = execute(
                resolved, streams, backend=backend, scratch=self.scratch,
                trace=trace, routes=routes,
            )
        except Exception as err:
            if backend == "host" or fo is None:
                raise
            if trace is not None:
                trace.clear()
            routes.clear()
            out = execute(
                resolved, streams, backend="host", scratch=self.scratch,
                trace=trace, routes=routes,
            )
            fo.record_failure(backend, err)  # host succeeded: backend-specific
        else:
            if backend != "host" and fo is not None:
                fo.record_success(backend)
        with self._stats_lock:
            nodes = self.stats["nodes"]
            for (by, name), k in routes.items():
                per = nodes.setdefault(by, {})
                per[name] = per.get(name, 0) + k
        return out

    def _compress_single(
        self,
        streams: List[Stream],
        backend: str,
        trace: Optional[List[Tuple[str, int]]] = None,
    ) -> bytes:
        resolved, was_hit = _resolve_impl(
            self.plan, streams, self.ctx, use_cache=self.use_resolve_cache
        )
        try:
            return self._execute(resolved, streams, backend, trace)
        except Exception:
            # A cached resolution is keyed on stream *shape*, but a selector's
            # choice can be inapplicable to new *values* of the same shape
            # (e.g. range_pack over a >57-bit range).  Re-expand for this
            # data; a failure on a fresh resolution is a genuine error.
            if not was_hit or self.plan.is_resolved:
                raise
            if trace is not None:
                trace.clear()  # the failed attempt's steps are not part of it
            fresh, _ = _resolve_impl(self.plan, streams, self.ctx, use_cache=False)
            return self._execute(fresh, streams, backend, trace)

    def compress_traced(
        self,
        inputs: Union[Stream, bytes, Sequence[Stream]],
        *,
        backend: Optional[str] = None,
    ) -> Tuple[bytes, List[Tuple[str, int]], float]:
        """Session-scoped evaluation call: one unchunked frame, instrumented.

        Returns ``(frame, trace, seconds)`` where ``trace`` is the executed
        ``(codec_name, input_bytes)`` list and ``seconds`` the wall-clock
        resolve+execute time from ``time.perf_counter``.  The frame is byte-identical to
        ``compress(..., chunk_bytes=0)``.  This is the trainer's candidate
        evaluation path: the trace feeds its *deterministic* cost model, the
        timing its reporting.
        """
        streams = [s.validate() for s in _as_streams(inputs)]
        trace: List[Tuple[str, int]] = []
        t0 = time.perf_counter()
        frame = self._compress_single(streams, backend or self.backend, trace)
        dt = time.perf_counter() - t0
        self._bump(
            calls=1,
            bytes_in=sum(s.nbytes for s in streams),
            bytes_out=len(frame),
        )
        return frame, trace, dt

    # ----------------------------------------------------------- streaming
    def compress_chunks(
        self,
        chunks: Iterable[Stream],
        out: BinaryIO,
        *,
        n_chunks: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> int:
        """Pipelined core: parallel encode, in-order incremental container
        write.  Returns bytes written.  With ``n_chunks`` known the output is
        byte-identical to ``write_container`` over the same frames; without
        it, ``out`` must be seekable (see :class:`wire.ContainerWriter`).

        At most :attr:`window` chunks (plus their encoded frames) are held in
        memory at once — the input may be an unbounded lazy iterator.
        """
        backend = backend or self.backend
        if self.ctx.format_version < CONTAINER_MIN_VERSION:
            raise ValueError(
                f"chunked compression requires format version"
                f" >= {CONTAINER_MIN_VERSION} (at {self.ctx.format_version})"
            )
        it = iter(chunks)
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("compress_chunks needs at least one chunk") from None
        # resolve once on the first chunk; workers fall back per chunk on a
        # data-dependent refusal, exactly like the one-shot chunked path
        resolved = resolve(
            self.plan, [first], self.ctx, use_cache=self.use_resolve_cache
        )

        def _one(ch: Stream) -> bytes:
            try:
                return self._execute(resolved, [ch], backend)
            except Exception:
                fresh = resolve(self.plan, [ch], self.ctx, use_cache=False)
                return self._execute(fresh, [ch], backend)

        writer = wire.ContainerWriter(out, self.ctx.format_version, n_chunks)
        for frame in self._window_map(_one, it, head=[first]):
            writer.write_chunk(frame)
            self._bump(chunks=1)
        return writer.close()

    def compress_to(
        self, inputs: Union[Stream, bytes, Sequence[Stream]], out: BinaryIO
    ) -> int:
        """Compress straight into a binary sink (single frame or container).

        Mirrors :meth:`compress` — same bytes, same errors — but never
        materializes the whole container: a multi-chunk input streams through
        :meth:`compress_chunks`.
        """
        cb = self.chunk_bytes
        streams = [s.validate() for s in _as_streams(inputs)]
        if cb:
            if len(streams) != 1:
                raise ValueError("chunked compression supports exactly one input")
            if self.ctx.format_version < CONTAINER_MIN_VERSION:
                raise ValueError(
                    f"chunk_bytes requires format version >= {CONTAINER_MIN_VERSION}"
                    f" (compressing at {self.ctx.format_version})"
                )
        chunks = _split_chunks(streams[0], cb) if cb else []
        if len(chunks) > 1:
            self._bump(calls=1, bytes_in=streams[0].nbytes)
            n = self.compress_chunks(chunks, out, n_chunks=len(chunks))
            self._bump(bytes_out=n)
            return n
        frame = self.compress(streams, chunk_bytes=0)
        out.write(frame)
        return len(frame)

    # ---------------------------------------------------------- inspection
    def resolved(self, inputs) -> ResolvedPlan:
        """Phase-1 artifact for these inputs (cached like compress())."""
        return resolve(self.plan, inputs, self.ctx, use_cache=self.use_resolve_cache)


class DecompressorSession(_SessionBase):
    """The universal decoder as a long-lived session.

    Plan-free by construction (frames are self-describing); what persists is
    the decode-side coder-table scratch and the thread pool that fans
    container chunks out.  ``decompress()`` matches the module-level function;
    :meth:`iter_frames` / :meth:`decompress_from` add the bounded-memory
    streaming path over ``wire.iter_container_frames``.
    """

    def __init__(
        self,
        *,
        n_workers: Optional[int] = None,
        window: Optional[int] = None,
        table_cache_size: int = 256,
        scratch: Optional[ExecScratch] = None,
    ):
        super().__init__(n_workers, window, table_cache_size, "ozl-dec", scratch)

    def _one(self, frame: bytes) -> List[Stream]:
        with self.scratch.activate():
            return _decompress_single(frame)

    def decompress(self, frame: bytes) -> List[Stream]:
        """Frame or container -> regenerated input streams."""
        self._bump(calls=1, bytes_in=len(frame))
        if wire.is_container(frame):
            version, sub_frames = wire.read_container(frame)
            check_decode_version(version)
            if not sub_frames:
                raise wire.FrameError("empty container")
            if len(sub_frames) > 1:
                parts = list(self._window_map(self._one, sub_frames))
            else:
                parts = [self._one(sub_frames[0])]
            for p in parts:
                if len(p) != 1:
                    raise wire.FrameError(
                        "container chunks must be single-input frames"
                    )
            self._bump(chunks=len(parts))
            out = [_concat_decoded([p[0] for p in parts])]
        else:
            out = self._one(frame)
            self._bump(chunks=1)
        self._bump(bytes_out=sum(s.nbytes for s in out))
        return out

    # ----------------------------------------------------------- streaming
    def iter_frames(self, reader: BinaryIO) -> Iterator[Stream]:
        """Yield each container chunk's regenerated stream, in order, decoding
        up to :attr:`window` chunks concurrently with bounded memory.  A bare
        (non-container) frame yields its single stream.

        Chunk type consistency is enforced across the container; the trailing
        container CRC is verified by the underlying frame iterator before the
        final chunk is processed, and every chunk frame's own CRC is verified
        as it is decoded (fail closed, no silent partial output).
        """
        head = reader.read(4)
        rest = _Prefixed(head, reader)
        if head == wire.CONTAINER_MAGIC:
            # keep only (stype, width) of the first chunk, not its data —
            # holding the Stream would pin a whole extra chunk in memory
            ref_meta: Optional[Tuple[SType, int]] = None
            for part in self._window_map(
                self._one, wire.iter_container_frames(rest)
            ):
                if len(part) != 1:
                    raise wire.FrameError(
                        "container chunks must be single-input frames"
                    )
                (s,) = part
                if ref_meta is None:
                    ref_meta = (s.stype, s.width)
                elif (s.stype, s.width) != ref_meta:
                    raise wire.FrameError(
                        "container chunks disagree on stream type"
                    )
                self._bump(chunks=1)
                yield s
        else:
            blob = rest.read()
            for s in self.decompress(blob):
                yield s

    def decompress_from(self, reader: BinaryIO) -> List[Stream]:
        """Streaming read + decode, then concatenate (one materialized copy).

        A bare (non-container) frame decodes as-is — its streams are distinct
        graph inputs, never concatenated."""
        head = reader.read(4)
        rest = _Prefixed(head, reader)
        if head != wire.CONTAINER_MAGIC:
            return self.decompress(rest.read())
        parts = list(self.iter_frames(rest))
        if not parts:
            raise wire.FrameError("empty container")
        self.stats["calls"] += 1
        return [_concat_decoded(parts)]

    # -------------------------------------------------------------- salvage
    def decompress_salvage(
        self, src: Union[bytes, BinaryIO]
    ) -> Tuple[List[Stream], "wire.SalvageReport"]:
        """Best-effort decode of a damaged frame/container (recovery path).

        Returns ``(streams, report)``: one regenerated stream per recovered
        container chunk, in chunk order, plus the
        :class:`~repro.core.wire.SalvageReport` saying exactly which chunk
        indices survived and which ranges were lost.  Unlike
        :meth:`decompress` this never raises on damage — an unrecoverable
        record simply returns no streams and a report explaining why.  The
        whole record is held in memory; the default fail-closed readers
        remain the right tool for intact data.
        """
        data = src if isinstance(src, (bytes, bytearray)) else src.read()
        data = bytes(data)
        self._bump(calls=1, bytes_in=len(data))
        if not wire.is_container(data):
            # a bare frame has no chunk redundancy: decode or report, per its
            # own CRC — there is nothing to resynchronize on
            report = wire.SalvageReport(n_chunks=1)
            try:
                out = self._one(data)
                report.recovered.append(0)
                report.trailer_ok = True
                self._bump(chunks=1, bytes_out=sum(s.nbytes for s in out))
                return out, report
            except Exception as err:
                report.damaged.append((0, 0))
                report.trailer_ok = False
                report.notes.append(f"bare frame unrecoverable: {err}")
                return [], report
        frames, report = wire.salvage_container(data)

        def _try(frame: bytes) -> Optional[List[Stream]]:
            try:
                return self._one(frame)
            except Exception:
                return None

        parts = list(self._window_map(_try, frames)) if frames else []
        # when every recovered chunk has an exact index, frames and
        # report.recovered align (both in chunk order): a CRC-valid chunk
        # that still fails to decode moves from recovered to damaged
        aligned = (
            report.recovered_unplaced == 0
            and len(parts) == len(report.recovered)
        )
        out = []
        failed_idx: List[int] = []
        failed = 0
        for j, part in enumerate(parts):
            if part is None or len(part) != 1:
                failed += 1
                if aligned:
                    failed_idx.append(report.recovered[j])
                continue
            out.append(part[0])
        if failed:
            for i in failed_idx:
                report.recovered.remove(i)
                report.damaged.append((i, i))
            report.damaged.sort(key=lambda r: r[0])
            report.notes.append(f"{failed} recovered chunk(s) failed to decode")
        self._bump(chunks=len(out), bytes_out=sum(s.nbytes for s in out))
        return out, report


class SessionPool:
    """Thread-safe checkout pool of sessions keyed by plan digest.

    The serving layer keeps one entry per registered plan: a factory plus a
    bounded set of lazily created :class:`CompressorSession` objects.
    ``acquire(key)`` is a context manager that checks a session out for one
    request and returns it on exit; when every session of a key is in use the
    caller *blocks* until one frees — which is the service's first line of
    backpressure (the second is each session's bounded in-flight window).

    A session that dies mid-request (the context body raised) is closed and
    dropped rather than returned, so a poisoned pool member can never serve a
    later request; the next acquire simply builds a fresh one.
    """

    def __init__(self, max_per_key: int = 4):
        if max_per_key < 1:
            raise ValueError("max_per_key must be >= 1")
        self.max_per_key = max_per_key
        self._lock = threading.Condition()
        self._factories: Dict[str, Callable[[], "CompressorSession"]] = {}
        self._idle: Dict[str, List["CompressorSession"]] = {}
        self._created: Dict[str, int] = {}
        self._counters: Dict[str, Dict[str, int]] = {}

    def register(self, key: str, factory: Callable[[], "CompressorSession"]) -> None:
        """Associate ``key`` (a plan digest/id) with a session factory."""
        with self._lock:
            self._factories[key] = factory
            self._idle.setdefault(key, [])
            self._created.setdefault(key, 0)
            self._counters.setdefault(
                key, {"acquires": 0, "creates": 0, "waits": 0, "drops": 0}
            )

    def keys(self) -> List[str]:
        with self._lock:
            return sorted(self._factories)

    def acquire(self, key: str, timeout: Optional[float] = None):
        """Context manager: check a session for ``key`` out of the pool."""
        return _PoolLease(self, key, timeout)

    def _checkout(self, key: str, timeout: Optional[float]) -> "CompressorSession":
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            if key not in self._factories:
                raise KeyError(f"no session factory registered for {key!r}")
            self._counters[key]["acquires"] += 1
            while True:
                if key not in self._factories:  # close()d while we waited
                    raise KeyError(
                        f"session pool closed while waiting for {key!r}"
                    )
                if self._idle[key]:
                    return self._idle[key].pop()
                if self._created[key] < self.max_per_key:
                    self._created[key] += 1
                    self._counters[key]["creates"] += 1
                    factory = self._factories[key]
                    break  # create outside the lock: factories may be slow
                self._counters[key]["waits"] += 1
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"no free session for {key!r} after {timeout:.1f}s"
                    )
                self._lock.wait(remaining)
        try:
            return factory()
        except BaseException:
            with self._lock:
                if key in self._created:  # close() may have raced us
                    self._created[key] -= 1
                # notify_all: one Condition spans every key, so a targeted
                # notify could wake a waiter for a different key and strand
                # the one this capacity actually frees
                self._lock.notify_all()
            raise

    def _checkin(self, key: str, session: "CompressorSession", ok: bool) -> None:
        with self._lock:
            alive = key in self._factories  # close() may have dropped the key
            if ok and alive:
                self._idle[key].append(session)
                drop = None
            else:
                if alive:
                    self._created[key] = max(0, self._created[key] - 1)
                    self._counters[key]["drops"] += 1
                drop = session
            self._lock.notify_all()  # see _checkout: one Condition, many keys
        if drop is not None:
            drop.close()

    def stats(self) -> Dict[str, dict]:
        """Per-key counters: created/idle/in_use plus acquire telemetry."""
        with self._lock:
            return {
                key: {
                    "created": self._created[key],
                    "idle": len(self._idle[key]),
                    "in_use": self._created[key] - len(self._idle[key]),
                    **self._counters[key],
                }
                for key in self._factories
            }

    def total_in_use(self) -> int:
        """Checked-out sessions across every key (0 == nothing leaked)."""
        with self._lock:
            return sum(
                self._created[key] - len(self._idle[key])
                for key in self._factories
            )

    def close(self) -> None:
        """Shut down every idle session and forget all factories.  Sessions
        currently checked out are closed by their lease on return (their key
        is gone, so ``_checkin`` drops them)."""
        with self._lock:
            idle, self._idle = self._idle, {}
            self._factories.clear()
            self._created.clear()
            self._lock.notify_all()
        for sessions in idle.values():
            for s in sessions:
                s.close()

    def __enter__(self) -> "SessionPool":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class _PoolLease:
    """The checkout token ``SessionPool.acquire`` hands to a ``with`` block."""

    def __init__(self, pool: SessionPool, key: str, timeout: Optional[float]):
        self._pool = pool
        self._key = key
        self._timeout = timeout
        self._session: Optional[CompressorSession] = None

    def __enter__(self) -> "CompressorSession":
        self._session = self._pool._checkout(self._key, self._timeout)
        return self._session

    def __exit__(self, exc_type, exc, tb) -> None:
        session, self._session = self._session, None
        if session is not None:
            self._pool._checkin(self._key, session, ok=exc_type is None)


class _Prefixed:
    """A tiny reader that replays already-consumed prefix bytes."""

    def __init__(self, prefix: bytes, reader: BinaryIO):
        self._prefix = prefix
        self._reader = reader

    def read(self, n: int = -1) -> bytes:
        if not self._prefix:
            return self._reader.read(n)
        if n is None or n < 0:
            out, self._prefix = self._prefix + self._reader.read(), b""
            return out
        take, self._prefix = self._prefix[:n], self._prefix[n:]
        if len(take) < n:
            take += self._reader.read(n - len(take))
        return take


# ------------------------------------------------------------------ frontend
def compress(
    plan: Plan,
    inputs: Union[Stream, bytes, Sequence[Stream]],
    *,
    ctx: Optional[CompressionCtx] = None,
    backend: str = "host",
    chunk_bytes: Optional[int] = None,
    n_workers: Optional[int] = None,
    use_resolve_cache: bool = True,
) -> bytes:
    """Compress ``inputs`` with ``plan`` into a self-describing frame.

    A thin wrapper over a throwaway :class:`CompressorSession` — long-running
    callers should hold a session instead and skip the per-call pool and
    scratch construction.

    ``chunk_bytes=N`` splits a (single) large input into independent chunks
    compressed concurrently and stored in a multi-chunk container frame
    (format v4+); the universal decoder reassembles them transparently.
    ``chunk_bytes=0``/``None`` disables chunking.

    ``use_resolve_cache=False`` forces fresh selector expansion for this
    call.  The cache is keyed on stream *shape*, so cached choices can be
    suboptimal (never wrong — a hard refusal triggers re-expansion) for new
    values of a previously seen shape; measurement code that compares
    selector choices across streams should bypass it.
    """
    with CompressorSession(
        plan,
        ctx=ctx,
        backend=backend,
        chunk_bytes=chunk_bytes,
        n_workers=n_workers,
        use_resolve_cache=use_resolve_cache,
    ) as session:
        return session.compress(inputs)


def decompress(frame: bytes, *, n_workers: Optional[int] = None) -> List[Stream]:
    """The universal decoder (paper §III-D): frame -> regenerated inputs.

    Accepts both single frames and multi-chunk containers; container chunks
    decode concurrently and concatenate back into the original stream.  A thin
    wrapper over a throwaway :class:`DecompressorSession`.
    """
    with DecompressorSession(n_workers=n_workers) as session:
        return session.decompress(frame)


def _decompress_single(frame: bytes) -> List[Stream]:
    version, n_inputs, nodes, stored = wire.read_frame(frame)
    check_decode_version(version)

    edges: Dict[int, Stream] = dict(stored)
    # recompute each node's output edge ids (sequential assignment)
    counter = n_inputs
    out_ids_per_node: List[Tuple[int, ...]] = []
    for node in nodes:
        out_ids_per_node.append(tuple(range(counter, counter + node.n_out)))
        counter += node.n_out

    for node, out_ids in zip(reversed(nodes), reversed(out_ids_per_node)):
        try:
            spec = get_codec_by_id(node.codec_id)
        except KeyError:
            # fail closed: an unknown id is a frame from a newer writer (or
            # corruption), not a programming error — name the id and the gate
            raise wire.FrameError(
                f"frame v{version} references unknown codec id"
                f" {node.codec_id} — newer writer than this decoder"
                f" (or corrupt frame); min_version gating only covers"
                f" registered codecs"
            ) from None
        if spec.min_version > version:
            raise wire.FrameError(
                f"frame v{version} contains codec {spec.name!r}"
                f" (min_version {spec.min_version}) — corrupt frame?"
            )
        try:
            outs = [edges.pop(e) for e in out_ids]
        except KeyError as err:
            raise ValueError(f"corrupt frame: missing edge {err}") from None
        ins = spec.run_decode(outs, node.header)
        if len(ins) != len(node.inputs):
            raise ValueError(
                f"codec {spec.name} regenerated {len(ins)} inputs,"
                f" frame says {len(node.inputs)}"
            )
        for eid, s in zip(node.inputs, ins):
            if eid in edges:
                raise ValueError(f"corrupt frame: edge {eid} regenerated twice")
            edges[eid] = s

    try:
        return [edges[i] for i in range(n_inputs)]
    except KeyError as err:
        raise ValueError(f"corrupt frame: input edge {err} not regenerated") from None


def decompress_bytes(frame: bytes) -> bytes:
    """Single-input convenience: regenerate and return the raw content bytes."""
    (out,) = decompress(frame)
    return out.content_bytes()


class Compressor:
    """A deployable compressor: plan + default ctx + stats (public API facade)."""

    def __init__(
        self,
        plan: Plan,
        *,
        format_version: int = CURRENT_FORMAT_VERSION,
        level: int = 5,
        name: str = "",
        backend: str = "host",
        chunk_bytes: Optional[int] = None,
    ):
        self.plan = plan.validate()
        self.format_version = check_compress_version(format_version)
        self.level = level
        self.name = name or plan.name
        self.backend = backend
        self.chunk_bytes = chunk_bytes

    def _ctx(self) -> CompressionCtx:
        return CompressionCtx(self.format_version, self.level)

    def compress(
        self,
        inputs,
        *,
        backend: Optional[str] = None,
        chunk_bytes: Optional[int] = None,
    ) -> bytes:
        """``chunk_bytes`` overrides the instance default; pass 0 to force an
        unchunked frame from a chunking-enabled compressor."""
        return compress(
            self.plan,
            inputs,
            ctx=self._ctx(),
            backend=backend or self.backend,
            chunk_bytes=self.chunk_bytes if chunk_bytes is None else chunk_bytes,
        )

    def resolve(self, inputs) -> ResolvedPlan:
        """Expose phase 1 for inspection/warm-up (cached like compress())."""
        return resolve(self.plan, inputs, self._ctx())

    def session(self, **overrides) -> "CompressorSession":
        """A long-lived streaming session with this compressor's settings.

        Keyword overrides (``backend=``, ``chunk_bytes=``, ``n_workers=``,
        ``window=``, ...) are passed through to :class:`CompressorSession`.
        """
        kw = dict(
            ctx=self._ctx(), backend=self.backend, chunk_bytes=self.chunk_bytes
        )
        kw.update(overrides)
        return CompressorSession(self.plan, **kw)

    @staticmethod
    def decompress(frame: bytes) -> List[Stream]:
        return decompress(frame)

    def roundtrip_check(self, inputs) -> bool:
        """Encode+decode and verify bit-exactness (used by tests & the trainer)."""
        if isinstance(inputs, (bytes, bytearray)):
            inputs = [serial(inputs)]
        elif isinstance(inputs, Stream):
            inputs = [inputs]
        frame = self.compress(list(inputs))
        outs = decompress(frame)
        if len(outs) != len(inputs):
            return False
        for a, b in zip(inputs, outs):
            if a.stype != b.stype or a.width != b.width:
                return False
            if a.content_bytes() != b.content_bytes():
                return False
            if a.stype.name == "STRING" and not np.array_equal(a.lengths, b.lengths):
                return False
        return True

    def serialize(self) -> bytes:
        from .serialize import serialize_plan

        return serialize_plan(
            self.plan,
            name=self.name,
            format_version=self.format_version,
            level=self.level,
        )

    @staticmethod
    def deserialize(blob: bytes) -> "Compressor":
        from .serialize import deserialize_plan

        plan, meta = deserialize_plan(blob)
        return Compressor(
            plan,
            name=meta.get("name", ""),
            format_version=meta.get("format_version", CURRENT_FORMAT_VERSION),
            level=meta.get("level", 5),
        )
