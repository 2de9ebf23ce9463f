"""TPUEngine: continuous-batching inference on one chip/mesh.

The scheduler thread owns the device state (a paged KV cache: one page pool
all rows share, models/decoding_paged.py) and runs the classic
continuous-batching loop (admit → prefill into a free slot's pages → global
decode step → emit/eject), all on static shapes:

- prompt lengths are padded to power-of-two buckets → a handful of prefill
  compilations, cached forever,
- the decode hot loop is ONE jitted fixed-shape program regardless of which
  rows are live — joins/leaves are slot bookkeeping, not recompiles,
- sampling is on-device; only the sampled token ids cross PCIe each step.

(reference capability: vLLM engine wrapped at
llm/_internal/serve/engines/vllm/vllm_engine.py:114; TPU design is
greenfield per SURVEY.md §7 — static-shape bucketing and a ragged Pallas
decode kernel instead of paged CUDA kernels.)
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import logging
import queue
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import ops
from ray_tpu._private import accelerators
from ray_tpu.exceptions import DeadlineExceededError, RequestCancelledError
from ray_tpu.models import decoding
from ray_tpu.models import decoding_paged as dp
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops.ragged_paged_attention import table_width, walked_positions
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    stop_token_ids: tuple = ()
    # constrained decoding: a llm.guided.GuidedFSM over token ids
    # (reference: guided_decoding passthrough to vLLM structured output,
    # vllm_engine_stage.py:278) — see ray_tpu/llm/guided.py
    guided: object | None = None


@dataclasses.dataclass
class _Request:
    rid: int
    tokens: list
    params: SamplingParams
    out_queue: queue.SimpleQueue = dataclasses.field(default_factory=queue.SimpleQueue)
    slot: int = -1
    generated: int = 0
    # tokens sampled for this row on the device, read or not: 1 at the bind
    # (the prefill's, or the one transferred), one more a decode step
    # dispatched. Runs ahead of `generated` by what is still unread; the
    # host's mirror of the row's device length is length0 + dispatched - 1
    dispatched: int = 0
    # the client's stream is closed (end, error or abort): tokens of this
    # request still unread are dropped
    finished: bool = False
    kv_pack: dict | None = None  # prefilled elsewhere (PD disaggregation)
    # streamed PD admission: pages adopted as they arrive off the transfer
    # plane (kv_transfer.KVPageStream protocol); length0 mirrors the
    # row's device length host-side so the ragged decode step can bound
    # its page sweep without a device readback
    kv_stream: object | None = None
    length0: int = 0
    # chunked-prefill progress (engine._prefill_step)
    pf_done: int = 0
    pf_pages: list | None = None
    pf_hashes: list | None = None
    # state-space layers: the recurrent state after the chunks run so far
    pf_state: dict | None = None
    # request-phase stamps (wall clock): submit → slot and pages granted
    # (scheduled) is the queue wait, → first token the prefill, → release
    # the decode; submit → decode-slot bind is the admission wait; _emit
    # tracks the inter-token gap off last_emit_ts. Read at release for the
    # engine's sums and spans, and by llm/pd.py decode_stream to emit
    # retroactive phase spans.
    submitted_ts: float = 0.0
    scheduled_ts: float = 0.0
    admitted_ts: float = 0.0
    first_token_ts: float = 0.0
    last_emit_ts: float = 0.0
    # the span context active at submit() (None = request not sampled):
    # the engine:* phase spans hang under it when the request is released
    trace_ctx: dict | None = None
    pf_chunks: int = 0        # prefill chunks run (chunked prefill)
    prefix_reused: int = 0    # prompt tokens served by the prefix cache
    # multi-LoRA: bank index this request decodes with (0 = base model)
    lora_idx: int = 0
    lora_released: bool = False
    # absolute wall-clock deadline (0 = none): the scheduler aborts the
    # row between steps once expired, and refuses admission for a request
    # whose queue-wait already spent the budget
    deadline_ts: float = 0.0

    def __iter__(self):
        """Yield generated tokens as they are produced (public surface for
        callers holding a submit() result — no private imports needed)."""
        return _iter_request(self)


_SENTINEL = object()


class _EngineError:
    """End-of-stream marker carrying the scheduler's failure."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _RequestError(_EngineError):
    """End-of-stream marker for a PER-REQUEST failure (e.g. the KV
    transfer feeding a streamed admission died): the carried exception is
    re-raised to this caller; the engine and every other request keep
    serving."""


def _iter_request(req: "_Request"):
    """Yield a request's tokens; raise if the engine died mid-stream."""
    while True:
        tok = req.out_queue.get()
        if tok is _SENTINEL:
            return
        if isinstance(tok, _RequestError):
            raise tok.exc
        if isinstance(tok, _EngineError):
            raise RuntimeError("engine scheduler died mid-generation") from tok.exc
        yield tok


@dataclasses.dataclass
class _Unread:
    """Tokens sampled on the device and not yet read on the host: a decode
    step's (one a slot) or a prefill's first. `rows` pairs an index into
    `toks` with the REQUEST that held it when the program was dispatched: by
    the time the tokens are read the slot may be another request's. `wait` is
    the loop phase its fetch is timed as; a decode step's is `decode_wait`.
    A step also says what its pass put on the device before it: `kind`
    (`PASS_KINDS`) and the padded prompt tokens of those programs."""
    toks: Any
    rows: list
    wait: str
    t_dispatch: float = 0.0
    exit_cdf: Any = None
    kind: str = "step"
    prompt_tokens: int = 0
    # `ops.share_counts` of the programs dispatched since the unread before
    expert_counts: tuple = ()

    @property
    def step(self) -> bool:
        return self.wait == "decode_wait"


# The scheduler thread's wall time, cut into phases that never overlap and
# leave nothing out (PERF.md section 3 has the table). "host" is every
# phase but `parked` and the three `*_wait`, which block on a device→host
# fetch; dispatch is asynchronous, so device time queued in one phase is
# paid in the next wait, whichever program it belongs to. The loop keeps one
# decode step in flight, so `decode_wait` is the fetch of the step BEFORE
# the one just dispatched, and `admit_wait` / `prefill_wait` the fetch of a
# first token after the decode step that follows its prefill has gone out.
# A host phase is WORK only while its dispatches return at once: with the
# device's queue never empty a dispatch can stand in the runtime, so the
# clock also times every dispatch by program (`dispatch_s`, inside its
# phase) and reads the thread's CPU time where it enters and leaves the host
# phases (`host_cpu_s`): host_s = work_s + dispatch_s, and host_s -
# host_cpu_s is the time the thread stood in a host phase off the CPU
# (blocked, or without the GIL).
LOOP_PHASES = ("parked", "sweep", "admit", "admit_wait", "streams",
               "prefill", "prefill_wait", "decode", "decode_wait", "emit")
HOST_PHASES = tuple(p for p in LOOP_PHASES
                    if p != "parked" and not p.endswith("_wait"))
_HOST = frozenset(HOST_PHASES)
# A decode step by what its pass dispatched before it: nothing, or a chunk,
# an unstaged prompt's prefill or transferred pages.
PASS_KINDS = ("step", "step_prefill")


class _Dispatch:
    """One program's entry of `_PhaseClock.dispatch`: a context manager kept
    for the clock's life and entered around each call that hands the device
    that program, on the scheduler thread alone (never nested)."""

    __slots__ = ("row", "_annotation", "_span", "_t0")

    def __init__(self, program: str):
        self.row = {"calls": 0, "seconds": 0.0}
        self._annotation = tracing.device_annotation("engine:dispatch:" + program)

    def __enter__(self):
        self._span = self._annotation()
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        took = time.perf_counter() - self._t0
        self._span.__exit__(None, None, None)
        self.row["calls"] += 1
        self.row["seconds"] += took


class _PhaseClock:
    """The scheduler thread's one clock (plain floats and ints, no lock;
    `snapshot()` may be called from any thread).

    `mark(phase)` closes the open phase and opens the next: one
    `perf_counter` read a boundary, the wall seconds to `seconds`. The same
    boundaries are `ray_tpu:engine:<phase>` spans on the JAX profiler's
    timeline when a trace is being taken. Where the thread enters or leaves
    the host phases (a fetch, or parking: twice a decode pass) the boundary
    also reads `thread_time`, a system call, and books the thread's CPU
    seconds of the host stretch it closes to `host_cpu_s`.

    `dispatch(program)` times one call that hands the device something,
    INSIDE the open phase (whose seconds still include it): two
    `perf_counter` reads, a `ray_tpu:engine:dispatch:<program>` span nested
    in the phase's. A call that blocks lies over the device ops it waited
    for.

    `book_pass()` keeps the interval between the arrival of two decode
    steps' tokens by the step's kind (`PASS_KINDS`): with a step always
    queued behind its predecessor that interval is the device's time for
    what the pass dispatched."""

    def __init__(self):
        self.seconds = dict.fromkeys(LOOP_PHASES, 0.0)
        self._spans = {p: tracing.device_annotation("engine:" + p)
                       for p in LOOP_PHASES}
        self._span = None
        self.started = time.perf_counter()
        self._open = ("parked", self.started)  # the open phase, since when
        # the thread's CPU clock where it entered the host phases it is in;
        # None in a wait or parked
        self._cpu_since: float | None = None
        self.host_cpu_s = 0.0
        self._dispatches: dict[str, _Dispatch] = {}
        self.passes = {k: {"count": 0, "seconds": 0.0, "rows": 0,
                           "prompt_tokens": 0} for k in PASS_KINDS}

    @property
    def phase(self) -> str:
        return self._open[0]

    def mark(self, phase: str) -> float:
        now = time.perf_counter()
        was, since = self._open
        self.seconds[was] += now - since
        self._open = (phase, now)
        if (phase in _HOST) != (self._cpu_since is not None):
            cpu = time.thread_time()
            if self._cpu_since is None:
                self._cpu_since = cpu
            else:
                self.host_cpu_s += cpu - self._cpu_since
                self._cpu_since = None
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span = self._spans[phase]()
        self._span.__enter__()
        return now

    def dispatch(self, program: str) -> _Dispatch:
        entry = self._dispatches.get(program)
        if entry is None:
            entry = self._dispatches[program] = _Dispatch(program)
        return entry

    def book_pass(self, kind: str, seconds: float, rows: int,
                  prompt_tokens: int) -> None:
        p = self.passes[kind]
        p["count"] += 1
        p["seconds"] += seconds
        p["rows"] += rows
        p["prompt_tokens"] += prompt_tokens

    def snapshot(self) -> dict:
        """Seconds per phase so far, the open phase's running time included,
        with their `host_s` (HOST_PHASES) and `active_s` (all but `parked`)
        sums and the thread's wall time. A boundary that falls inside the
        copy leaves that one phase interval out; readers take deltas over
        seconds. `host_cpu_s` is of closed host stretches only: `thread_time`
        is the calling thread's own, so the CPU time of the stretch the
        thread is in (at most one pass's host phases) is left out.
        `dispatch_s` is inside `host_s`; `work_s` is the rest of it."""
        # the sums that lie INSIDE the phases are read before the phases,
        # so a reading taken mid-pass never shows more of them than of those
        dispatch = {program: dict(d.row) for program, d
                    in list(self._dispatches.items())}
        dispatch_s = sum(row["seconds"] for row in dispatch.values())
        host_cpu_s = self.host_cpu_s
        seconds = dict(self.seconds)
        phase, since = self._open
        now = time.perf_counter()
        seconds[phase] += now - since
        host = sum(seconds[p] for p in HOST_PHASES)
        return {"seconds": seconds, "host_s": host,
                "active_s": host + sum(seconds[p] for p in LOOP_PHASES
                                       if p.endswith("_wait")),
                "thread_s": now - self.started,
                "host_cpu_s": host_cpu_s,
                "dispatch_s": dispatch_s, "work_s": host - dispatch_s,
                "dispatch": dispatch,
                "passes": {k: dict(p) for k, p in self.passes.items()}}


def bucket_for(n: int, min_bucket: int, max_len: int) -> int:
    """Smallest power-of-two bucket ≥ n (starting at min_bucket, capped at
    max_len). Shared by the engine and the PD prefill server so the two can
    never disagree on padded shapes."""
    b = min_bucket
    while b < n and b < max_len:
        b *= 2
    return min(b, max_len)


def _shard_params_tp(params, mesh):
    """Tensor-parallel placement of the transformer parameter tree over a
    1-axis mesh: attention head dims and MLP hidden dims split, everything
    else replicated. XLA propagates + inserts the collectives."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = mesh.axis_names[0]

    def spec_for(path, x):
        name = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                        for p in path)
        nd = x.ndim
        def pad(spec):
            return P(*(list(spec) + [None] * (nd - len(spec))))
        if "mlp" in name:
            # transformer.py MLP names: wi / wi_gate / wi_up [L, E, F],
            # wo [L, F, E], bi [L, F] — split the hidden (F) dim
            if "wi" in name:
                return pad([None, None, axis])
            if "wo" in name or name.endswith("bi"):
                return pad([None, axis])
            return P()
        if "wq" in name or "wk" in name or "wv" in name:
            # stacked [L, E, H, Dh] → split heads
            return pad([None, None, axis])
        if "wo" in name:
            # attention out [L, H, Dh, E] → split heads
            return pad([None, axis])
        if "bq" in name or "bk" in name or "bv" in name:
            return pad([None, axis])
        return P()  # replicate

    def place(path, x):
        return jax.device_put(x, NamedSharding(mesh, spec_for(path, x)))

    return jax.tree_util.tree_map_with_path(place, params)


def _shard_state_tp(state, mesh):
    """Page pools split on the kv-head dim; bookkeeping replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = mesh.axis_names[0]
    pool = P(None, None, None, axis)     # [L, pages, P, Hkv, Dh]
    return {k: jax.device_put(
                v, NamedSharding(mesh, pool if k in ("kp", "vp") else P()))
            for k, v in state.items()}


class TPUEngine:
    def __init__(self, cfg: TransformerConfig, params: Any, *,
                 max_slots: int = 8, max_len: int | None = None,
                 min_bucket: int = 32, seed: int = 0, page_size: int = 64,
                 num_pages: int | None = None,
                 max_prefills_per_step: int = 2,
                 enable_prefix_cache: bool = False,
                 prefill_chunk: int | None = None,
                 mesh=None, max_loras: int = 0, lora_rank: int = 8):
        accelerators.compile_cache_counts()  # start counting before compiling
        self.cfg = cfg
        self.max_len = max_len or cfg.max_seq_len
        if self.max_len > cfg.max_seq_len:
            raise ValueError(
                f"engine max_len {self.max_len} exceeds the model's "
                f"max_seq_len {cfg.max_seq_len} (rope/pos tables are sized "
                "by the model config)")
        self.max_slots = max_slots
        if (cfg.mla or cfg.n_dense_layers or cfg.window or cfg.n_passes > 1
                or cfg.sandwich_norms or cfg.ssm or cfg.kv_packed):
            # what is not carried to the latent cache, to two kinds of layer
            # in one stack, to a stack run several times and to a recurrent
            # state: refused here, not at the first request
            kinds = [what for on, what in (
                (cfg.mla, "latent attention (kv_lora_rank)"),
                (cfg.window, "window layers"),
                (cfg.n_dense_layers, "leading dense layers"),
                (cfg.ssm, "state-space layers (a recurrent state a row)"),
                (cfg.kv_packed, "KV heads packed a row of 128 lanes (kv_packed)"),
                (cfg.n_passes > 1, "a looped stack (n_passes)"),
                (cfg.sandwich_norms, "sandwich norms")) if on]
            # the first names the model as it always did; a stack that mixes
            # them (window layers after leading dense ones, sandwich norms)
            # names them all
            kind = kinds[0] if len(kinds) == 1 else " and ".join(
                [", ".join(kinds[:-1]), kinds[-1]])
            for on, what in ((mesh is not None, "a tensor-parallel mesh"),
                             (max_loras, "max_loras")):
                if on:
                    raise ValueError(
                        f"a model with {kind} is served on one chip, without "
                        f"{what}: the sharding of the page pool over kv "
                        "heads and the LoRA bank are built for per-head K "
                        "and V over one kind of layer, each applied once")
        if cfg.window and enable_prefix_cache:
            raise ValueError(
                "a model with window layers is served without "
                "enable_prefix_cache: a cached block would also have to pin "
                "the window layers' pages under it, and those lie in a ring "
                "the row writes over as it grows")
        if cfg.ssm and enable_prefix_cache:
            raise ValueError(
                "a model with state-space layers is served without "
                "enable_prefix_cache: a cached block is reusable only with the "
                "recurrent state at its end, and no snapshot of that state is "
                "kept beside a block's pages")
        if max_loras and (enable_prefix_cache or prefill_chunk is not None):
            raise ValueError(
                "max_loras cannot be combined with enable_prefix_cache or "
                "prefill_chunk: a cached block's hash does not name the "
                "adapter that wrote it, and the continuation prefill "
                "(prefill_with_prefix) applies none")
        if page_size <= 0 or (page_size & (page_size - 1)):
            raise ValueError("page_size must be a positive power of two")
        if self.max_len % page_size:
            raise ValueError(
                f"max_len {self.max_len} must be a multiple of "
                f"page_size {page_size} (buckets reshape into whole pages)")
        min_bucket = max(min_bucket, page_size)
        if min_bucket % page_size:
            raise ValueError(
                f"min_bucket {min_bucket} must be a multiple of "
                f"page_size {page_size} (every prompt bucket reshapes "
                f"into whole pages)")
        if prefill_chunk is not None:
            if (prefill_chunk < min_bucket
                    or prefill_chunk % page_size
                    or bucket_for(prefill_chunk, min_bucket, self.max_len)
                    != prefill_chunk):
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be one of the "
                    f"engine's bucket sizes (min_bucket {min_bucket} "
                    f"doublings) and a multiple of page_size "
                    f"{page_size} — a non-bucket chunk would pad past "
                    "its own page span and corrupt neighboring pages")
        self.buckets = []
        b = min_bucket
        while b < self.max_len:
            self.buckets.append(b)
            b *= 2
        self.buckets.append(self.max_len)
        # multi-chip serving: tensor-parallel sharding over a 1-axis mesh —
        # params' head/ff dims and the page pools' kv-head dim are split
        # across chips; XLA inserts the collectives (reference capability:
        # vLLM tensor_parallel_size via PG bundles, vllm_models.py:215 —
        # here it's jax.sharding over ICI instead of NCCL)
        self.mesh = mesh
        if mesh is not None:
            params = _shard_params_tp(params, mesh)
        self.params = params
        self.page_size = page_size
        self.max_pages_per_seq = self.max_len // page_size
        # default pool = full reservation (+1 scratch); pass num_pages
        # lower to oversubscribe HBM against short real sequences
        self.num_pages = num_pages or (max_slots * self.max_pages_per_seq + 1)
        # window layers (models/decoding_paged.py): a pool of their own and
        # a ring of at most `ring` of its pages a row, held for the row's life
        self.ring = (min(dp.window_ring(cfg, page_size, prefill_chunk),
                         self.max_pages_per_seq) if cfg.window else 0)
        self.state = dp.init_paged_state(
            cfg, max_slots, self.max_len, self.num_pages, page_size,
            ring=self.ring or None)
        # a step's exit CDF is read a step later, when the next step has been
        # given the state: it never stays in the state the programs donate
        self.state.pop("exit_cdf", None)
        # that pool's size is dp's: a ring for every slot (+ scratch)
        self.window_pages = self.state["wkp"].shape[1] if cfg.window else 0
        if mesh is not None:
            self.state = _shard_state_tp(self.state, mesh)
        self._free_pages = list(range(1, self.num_pages))  # 0 = scratch
        self._slot_pages: dict[int, list] = {}
        self._free_wpages = list(range(1, self.window_pages))  # 0 = scratch
        self._slot_wpages: dict[int, list] = {}
        # hash-block prefix cache over the SAME page pool (reference
        # capability: vLLM automatic prefix caching): chain-hashed
        # full prompt blocks map to pages still resident in HBM; a
        # repeated prefix skips its share of prefill compute entirely.
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self._prefix_cache: collections.OrderedDict = \
            collections.OrderedDict()        # block-chain hash → page id
        self._page_refs: dict[int, int] = {}  # shared page → live users
        self._page_hash: dict[int, bytes] = {}  # reverse map (eviction)
        self._slot_shared: dict[int, list] = {}  # slot → shared pages
        self.prefix_hits = 0       # requests that reused ≥1 block
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        # chunked prefill (reference capability: vLLM chunked prefill):
        # long prompts prefill in fixed chunks interleaved with decode
        # steps so running requests keep emitting during a long
        # admission instead of stalling a full prompt-bucket compile
        self.prefill_chunk = prefill_chunk
        self._prefilling: list = []  # requests mid-chunked-prefill
        self.prefill_chunks_run = 0
        # continuation prefills (a chunk past the first, or a suffix behind
        # shared pages) by the form their attention took (the flash launch or
        # the XLA form: `dp.continuation_blocks`), and the query-key pairs
        # their masks admit, summed over the layers
        self.continuations_kernel = 0
        self.continuations_xla = 0
        self.attended_pairs = 0
        # recurrent layers: the positions their chunked scan ran over in the
        # prefill programs dispatched (a span's bucket rounded up to whole
        # chunks of the scan, a layer), and those of them past the span's
        # real tokens; from what the host knows of a call, no device read
        self.scan_positions = 0
        self.scan_padded = 0
        # ... and those whose scan ran in the Pallas launch: all of them or
        # none, by what `ops.kda_chunk_scan` chooses for these shapes here
        self.scan_kernel_positions = 0
        self._scan_kernel = bool(self.cfg.kda and mesh is None and ops.ssm.kda_scan_in_kernel(
            self.cfg.ssm.n_heads, self.cfg.ssm.d_head, self.cfg.ssm.d_head, self.cfg.ssm.chunk))
        # ... and those whose mixer ran its elementwise work on either side of
        # the scan in its three launches (`transformer.kda_mixer`): asked here
        # at the smallest row block, and of each bucket where it is counted
        self.mixer_kernel_positions = 0
        self._mixer_kernel = bool(self.cfg.kda and mesh is None and ops.ssm.kda_mixer_in_kernel(
            ops.ssm.MIXER_ROWS, self.cfg.ssm.d_head, self.cfg.ssm.d_conv))
        # decode attention is one ragged-paged-attention launch over the
        # batch's live page tables (ops/ragged_paged_attention.py): the
        # Pallas kernel where the code can see a TPU and an unsharded pool,
        # the bit-consistent pure-JAX reference elsewhere (plain XLA ops,
        # which a tensor-parallel mesh partitions)
        self._ragged_kernel = (mesh is None
                               and jax.default_backend() == "tpu")
        self._decode_attn = "ragged_" + ("kernel" if self._ragged_kernel
                                         else "reference")
        # multi-LoRA serving (reference capability: LoRA adapters with
        # dynamic loading on serve multiplexing —
        # python/ray/llm/_internal/serve/utils/lora_serve_utils.py; here
        # adapters live in a device bank gathered per row inside the SAME
        # batched decode step — decoding.init_lora_bank)
        self.max_loras = int(max_loras)
        self.lora_rank = int(lora_rank)
        self.lora_bank = None
        self._slot_lora = None
        if self.max_loras:
            self.lora_bank = decoding.init_lora_bank(cfg, self.max_loras,
                                                     self.lora_rank)
            self._lora_free = list(range(1, self.max_loras + 1))
            self._lora_ids: dict[str, int] = {}   # name -> bank index
            self._lora_refs: dict[int, int] = {}  # index -> live requests
            self._slot_lora = jnp.zeros((max_slots,), jnp.int32)
            # serializes bank read-modify-write: concurrent loads from
            # replica threads must not lose each other's writes
            self._lora_lock = threading.Lock()
        self.decode_steps = 0
        self.decode_slot_steps = 0  # sum of active slots over decode steps
        # one decode step in flight: tokens dispatched and not yet read,
        # oldest first (a step's, and the first tokens of the prefills
        # dispatched since), how many steps went out while the step before
        # them was unread, row-steps whose token was dropped because the row
        # had stopped or been aborted by the time it was read, and rows
        # released by count whose last tokens are still unread
        self._unread: collections.deque = collections.deque()
        self.steps_ahead = 0
        self.tokens_discarded = 0
        self._closing = 0
        self._t_fetched = 0.0  # when the last decode step's tokens arrived
        # padded prompt tokens of the prefill programs (chunks, unstaged
        # prompts; 0 for transferred pages) dispatched since the last decode
        # step went out; None when there were none: the next step's kind
        self._pass_prefill: int | None = None
        # a looped stack on the record (stats()["loops"]): passes over the
        # stack the decode steps ran (n_passes a step: every row takes every
        # pass), and live rows by the pass at which their exit CDF first
        # reached a half: what leaving the loop early WOULD save
        self.stack_passes = 0
        self.exit_rows = np.zeros((cfg.n_passes,), np.int64)
        # the cache on the record, cumulative (stats()["cache"]): positions
        # the decode steps attended over, prefix tokens gathered out of the
        # pool for continuation prefills, pages held and pages in the pool
        # summed over decode steps
        self.context_tokens = 0
        self.prefix_tokens_gathered = 0
        self.page_steps_used = 0
        self.page_steps_total = 0
        # ... positions a window layer attended over (at most the window a
        # row a step), tokens held (live rows' lengths + what staged
        # prefills have written) and the bytes of every page granted in
        # both pools, summed over decode steps; window pages likewise. Sums
        # kept as rows advance, so a decode pass adds integers: the live
        # rows' positions (sum of length0 + generated), the part of them
        # beyond the window, the staged prefills' tokens
        self.window_context_tokens = 0
        # ... positions held by the blocks of pages that the per-head
        # launch walked on a full layer (ops/ragged_paged_attention.py: a
        # row's live pages rounded up to whole blocks), of which
        # context_tokens were attended
        self.ragged_block_positions = 0
        self.held_token_steps = 0
        self.held_byte_steps = 0
        self.window_page_steps_used = 0
        self.window_page_steps_total = 0
        self._live_tokens = 0
        self._live_beyond_window = 0
        self._staged_tokens = 0
        self._page_bytes, self._wpage_bytes = (
            sum(self.state[k].nbytes // self.state[k].shape[1]
                for k in names if k in self.state)
            for names in (("kp", "vp"), ("wkp", "wvp")))
        # state-space layers: a slot IS a state slot (decoding_paged.py), held
        # from admission to release whatever the row's length, so a free
        # slot is what admits a row and pages are the small part
        self._state_bytes_per_row = sum(
            self.state[k].nbytes // max_slots for k in ("ssm", "conv")
            if k in self.state)
        # padded tokens of the dispatched calls by the form their expert
        # layers took (stats()["experts"]); a dense model counts neither
        self.expert_tokens_sorted = 0
        self.expert_tokens_onehot = 0
        # a model whose expert layers hold a share of the experts
        # (MoEConfig.experts_held): the routed slots of the dispatched calls
        # and the expert-layer calls, counted here; and what the device counted
        # of them (`ops.share_counts`: slots whose expert is held, held experts
        # with a row), which every program returns beside its result and the
        # loop reads with the step's tokens (`_Unread.expert_counts`)
        self.expert_slots_routed = 0
        self.expert_calls = 0
        self.expert_counts = np.zeros((2,), np.int64)
        self._expert_counts_unread: list = []
        # device-resident per-row sampling params: updated only on admit,
        # not rebuilt/re-uploaded every decode step
        self._temps = jnp.zeros((max_slots,), jnp.float32)
        self._topks = jnp.zeros((max_slots,), jnp.int32)
        # what the LIVE rows ask of the sampler, kept on the host as rows
        # join and leave (_count_live): how many sample at all and the
        # `top_k` values of those, by value. A released slot's entries above
        # stay as they were, so the step's form is chosen from these and
        # never from the device: (sampling, k_bucket, its steps' counter)
        self._live_sampling = 0
        self._live_top_ks: collections.Counter = collections.Counter()
        self._sampler_form = (False, 0, "steps_argmax")
        self.sampler_steps = {"steps_argmax": 0, "steps_categorical": 0,
                              "steps_top_k": 0}
        # guided decoding: per-slot host-side FSM + current state; the only
        # per-step device traffic is the additive bias rows (llm/guided.py)
        self._guided_fsm: dict[int, object] = {}
        self._guided_state: dict[int, int] = {}
        self.max_prefills_per_step = max(1, int(max_prefills_per_step))
        self.key = jax.random.PRNGKey(seed)
        self._free = list(range(max_slots))
        self._by_slot: dict[int, _Request] = {}
        self._waiting: queue.SimpleQueue = queue.SimpleQueue()
        self._backlog: list = []  # admitted-later queue (page pressure)
        self._streaming: list = []  # slot granted, pages still streaming in
        self._rid = itertools.count()
        self._work = threading.Event()
        self._stop = False
        self._error: BaseException | None = None
        self._setup: dict | None = None  # from_config's record of the start
        # cancellation plane: abort_request() is called from request
        # threads; rids land here and the scheduler applies them at the
        # top of its next pass (slot + pages reclaimed in one step).
        # _abort_pending keeps rids whose request is still in _waiting
        # (a SimpleQueue can't be searched) until _admit pops them;
        # values are monotonic stamps so stale rids age out.
        self._abort_q: queue.SimpleQueue = queue.SimpleQueue()
        self._abort_pending: dict[int, float] = {}
        self.aborts = 0  # requests reclaimed via abort/deadline
        # where the scheduler thread's time and each request's time go,
        # cumulative, exported as stats()["loop"]
        self._clock = _PhaseClock()
        self.requests_scheduled = 0
        self.queue_wait_s = 0.0   # Σ scheduled − submitted
        self.first_tokens = 0
        self.prefill_s = 0.0      # Σ first token − scheduled
        # serving-phase instrumentation (queue wait, prefill, decode-slot
        # admission wait, inter-token gap): pre-bound histograms resolved
        # ONCE per engine — the per-token cost is one clock read + one
        # lock-free observe. None when RayConfig.serve_metrics is off (the
        # bench A/B baseline).
        try:
            from ray_tpu.serve import request_context as _rc

            (self._phase_queue, self._phase_prefill, self._phase_admit,
             self._phase_gap) = (
                _rc.phase_observer(_rc.ENGINE_PHASE, phase) for phase in (
                    "queue_wait", "prefill", "admission_wait", "inter_token"))
        except Exception:  # pragma: no cover — metrics must never gate boot
            self._phase_queue = self._phase_prefill = None
            self._phase_admit = self._phase_gap = None
        # per-decode-step wall time (between the arrival of two steps'
        # tokens, or from a step's dispatch where nothing was in flight: the
        # phase clock's reads), labelled with what the step's pass put on
        # the device before it (PASS_KINDS): the number `passes` books
        self._step_obs = None
        try:
            from ray_tpu.serve import request_context as _rc2
            from ray_tpu.util import metrics as met

            if _rc2.metrics_enabled():
                h = met.get_or_create(
                    met.Histogram, "ray_tpu_llm_decode_step_seconds",
                    "time between the arrival of two decode steps' tokens "
                    "by what the pass dispatched (step|step_prefill)",
                    boundaries=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                                0.05, 0.1, 0.25, 0.5, 1.0],
                    tag_keys=("pass",))
                self._step_obs = {k: h.bind({"pass": k}) for k in PASS_KINDS}
        except Exception:  # pragma: no cover — metrics must never gate boot
            self._step_obs = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tpu-engine")
        self._thread.start()

    # ---------------------------------------------------------------- public

    @classmethod
    def from_config(cls, llm_config) -> "TPUEngine":
        """Single construction point for server/PD/batch paths. It times
        the replica's start by stage (`stats()["setup"]`): each stamp says
        when the HOST got past, and no device work is waited for."""
        t = [accelerators.process_start_time(), time.time()]
        accelerators.compile_cache_counts()  # build_model compiles too
        backend = jax.default_backend()
        t.append(time.time())
        if llm_config.accelerator_type == "TPU" and backend != "tpu":
            raise RuntimeError(
                f"LLMConfig.accelerator_type='TPU' but this process computes "
                f"on {backend!r}: its worker was bound no chip (deploy "
                "through build_openai_app / ray_actor_options num_tpus, or "
                "set accelerator_type=None for a host-only engine)")
        ek = dict(llm_config.engine_kwargs)
        # options that went with the slot layout, the gather step and n-gram
        # speculation; configuration files still say kv_layout "paged"
        for key in ("kv_layout", "attn_impl", "speculative_k", "ngram_size"):
            if key in ek and (key, ek[key]) != ("kv_layout", "paged"):
                raise ValueError(
                    f"engine_kwargs[{key!r}]={ek[key]!r}: the option was "
                    "removed; the engine serves from the paged KV cache "
                    "with the ragged decode step and does not speculate")
        cfg, params = llm_config.build_model()
        t.append(time.time())
        compile_at = {"weights": accelerators.compile_cache_counts()["seconds"]}
        # the constructor's defaults are the defaults here too
        kw = {k: ek[k] for k in (
            "max_slots", "max_len", "min_bucket", "seed", "page_size",
            "num_pages", "max_prefills_per_step", "enable_prefix_cache",
            "prefill_chunk", "mesh", "max_loras", "lora_rank") if k in ek}
        lora_cfg = getattr(llm_config, "lora_config", None)
        if lora_cfg:
            kw.setdefault("max_loras", lora_cfg.max_num_adapters_per_replica)
            kw.setdefault("lora_rank", lora_cfg.lora_rank)
        engine = cls(cfg, params, **kw)
        t.append(time.time())
        compile_at["engine"] = accelerators.compile_cache_counts()["seconds"]
        engine._setup = {
            "t_process": t[0],
            "seconds": {**{stage: b - a for stage, a, b in zip(
                ("process", "backend", "weights", "engine"), t, t[1:])},
                "to_first_request": None},
            "compile_at": compile_at}
        return engine

    def _note_first_request(self, now: float) -> None:
        """The first request closes the record of the start: ready reported,
        the controller's probe, the route and the proxy lie between the
        constructor and now. (Two first requests at once both write, a
        moment apart.)"""
        setup = self._setup
        if not setup or setup["seconds"]["to_first_request"] is not None:
            return
        setup["compile_at"]["first_request"] = (
            accelerators.compile_cache_counts()["seconds"])
        setup["seconds"]["to_first_request"] = (
            now - setup["t_process"] - sum(
                v for v in setup["seconds"].values() if v is not None))

    def _check_alive(self):
        if self._error is not None:
            raise RuntimeError("engine scheduler died") from self._error
        if self._stop:
            raise RuntimeError("engine is shut down")

    def load_lora(self, name: str, weights: dict, *,
                  alpha: float | None = None) -> None:
        """Load adapter `name` into a free bank slot. `weights` are
        layer-stacked host arrays {"A_q": [L, E, r], "B_q": [L, r, H, Dh],
        "A_v": [L, E, r], "B_v": [L, r, Hkv, Dh]} (missing targets stay
        zero). Scale defaults to alpha/r with alpha=r (i.e. 1.0)."""
        if self.lora_bank is None:
            raise ValueError("engine built without max_loras")
        with self._lora_lock:
            if name in self._lora_ids:
                raise ValueError(f"lora {name!r} already loaded")
            if not self._lora_free:
                raise RuntimeError(
                    f"no free lora slots (max_loras={self.max_loras}); "
                    f"unload one of {sorted(self._lora_ids)}")
            idx = self._lora_free.pop()
            # shallow copy: writes below bind new arrays to the COPY, so a
            # mid-write failure (device OOM) leaves self.lora_bank the old,
            # fully-consistent bank — no partially-written slot
            bank = dict(self.lora_bank)
            # validate EVERY shape before writing any — a partial write
            # followed by a raise would leave stale weights in a slot the
            # free list hands to the next adapter
            for key in ("A_q", "B_q", "A_v", "B_v"):
                if key in weights:
                    want = bank[key].shape[0:1] + bank[key].shape[2:]
                    if np.asarray(weights[key]).shape != want:
                        self._lora_free.append(idx)
                        raise ValueError(
                            f"lora {name!r} {key} shape "
                            f"{np.asarray(weights[key]).shape} != {want} "
                            f"(rank {self.lora_rank}, layer-stacked)")
            try:
                for key in ("A_q", "B_q", "A_v", "B_v"):
                    if key in weights:
                        bank[key] = bank[key].at[:, idx].set(
                            jnp.asarray(np.asarray(weights[key]),
                                        bank[key].dtype))
                scale = 1.0 if alpha is None else float(alpha) / self.lora_rank
                bank["scale"] = bank["scale"].at[idx].set(scale)
            except Exception:
                # device-side failure mid-write (e.g. HBM OOM): the slot must
                # go back on the free list or max_loras shrinks by one per
                # failure. The partial writes only touched the copy, so the
                # engine keeps decoding with the old consistent bank.
                self._lora_free.append(idx)
                raise
            self.lora_bank = bank
            self._lora_ids[name] = idx
            self._lora_refs[idx] = 0

    def unload_lora(self, name: str) -> None:
        """Free `name`'s bank slot. Refuses while requests using it are
        live (submitted and not yet finished)."""
        if self.lora_bank is None:
            raise KeyError(f"lora {name!r} not loaded")
        with self._lora_lock:
            if name not in self._lora_ids:
                raise KeyError(f"lora {name!r} not loaded")
            idx = self._lora_ids[name]
            if self._lora_refs.get(idx, 0) > 0:
                raise RuntimeError(
                    f"lora {name!r} has {self._lora_refs[idx]} live requests")
            # zero into a copy first: if a device write fails midway the
            # registry is untouched (same discipline as load_lora)
            bank = dict(self.lora_bank)
            for key in ("A_q", "B_q", "A_v", "B_v"):
                bank[key] = bank[key].at[:, idx].set(0.0)
            bank["scale"] = bank["scale"].at[idx].set(0.0)
            self.lora_bank = bank
            del self._lora_ids[name]
            self._lora_refs.pop(idx, None)
            self._lora_free.append(idx)

    def list_loras(self) -> list:
        return sorted(self._lora_ids) if self.lora_bank is not None else []

    def _lora_release(self, req: _Request) -> None:
        if req.lora_idx and not req.lora_released:
            req.lora_released = True
            with self._lora_lock:
                self._lora_refs[req.lora_idx] = max(
                    0, self._lora_refs.get(req.lora_idx, 1) - 1)

    def submit(self, token_ids: list, params: SamplingParams | None = None,
               *, lora: str | None = None,
               deadline_ts: float = 0.0) -> _Request:
        self._check_alive()
        params = params or SamplingParams()
        if (params.guided is not None
                and params.guided.vocab_size != self.cfg.vocab_size):
            raise ValueError(
                f"guided FSM vocab {params.guided.vocab_size} != model "
                f"vocab {self.cfg.vocab_size}")
        token_ids = list(token_ids)
        if not token_ids:
            raise ValueError("empty prompt: at least one token is required")
        limit = self.max_len - params.max_tokens - 1
        if limit <= 0:
            raise ValueError("max_tokens leaves no room for the prompt")
        token_ids = token_ids[-limit:]
        need = self._pages_needed(len(token_ids),
                                  self._bucket(len(token_ids)),
                                  params.max_tokens)
        if need > self.num_pages - 1:  # page 0 is scratch
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.num_pages - 1}; raise num_pages or shrink "
                f"prompt/max_tokens")
        lora_idx = 0
        if lora is not None:
            if self.lora_bank is None:
                raise ValueError("engine built without max_loras")
            # resolve + take the reference atomically w.r.t. load/unload —
            # otherwise an eviction between the check and the increment
            # could reuse the bank index for a different adapter
            with self._lora_lock:
                if lora not in self._lora_ids:
                    raise KeyError(f"lora {lora!r} not loaded "
                                   f"(loaded: {sorted(self._lora_ids)})")
                lora_idx = self._lora_ids[lora]
                self._lora_refs[lora_idx] += 1
        req = _Request(next(self._rid), token_ids, params, lora_idx=lora_idx,
                       deadline_ts=float(deadline_ts or 0.0))
        req.submitted_ts = time.time()
        self._note_first_request(req.submitted_ts)
        req.trace_ctx = tracing.current_context()
        self._waiting.put(req)
        self._work.set()
        return req

    def submit_prefilled(self, *, length: int = 0, first_token: int = 0,
                         params: SamplingParams | None = None,
                         k_pages: list | None = None,
                         v_pages: list | None = None,
                         kv_stream=None,
                         deadline_ts: float = 0.0) -> _Request:
        """Admit a sequence whose prefill ran elsewhere (PD disaggregation).

        Two forms:
        - page-granular: k_pages/v_pages are ordered lists of
          [L, page_size, Hkv, Dh] pages (the shm transfer plane's unit).
          Each page is adopted into the page pool directly — no
          whole-bucket array is ever assembled;
        - streamed: kv_stream is a kv_transfer.KVPageStream the transfer
          plane is still feeding. The slot and its pages are granted NOW
          and each page is adopted the moment it arrives — the decode
          loop keeps stepping other slots while later pages stream in,
          and the row activates on the LAST page instead of waiting for
          pull-then-submit. A transfer failure surfaces as a per-request
          error; the slot and its granted pages are reclaimed.
        """
        self._check_alive()
        if (self.cfg.mla or self.cfg.window or self.cfg.n_passes > 1 or self.cfg.ssm
                or self.cfg.kv_packed):
            raise NotImplementedError(
                "submit_prefilled: the PD transfer plane (llm/pd.py, "
                "kv_transfer.py) moves per-head K and V pages of one kind of "
                "layer, a plane a layer; a model with latent attention caches "
                "one row a token, one with window layers a ring of pages on "
                "those layers, a looped stack a plane for every pass of every "
                "layer, one with state-space layers a recurrent state a row "
                "beside its pages (and packed KV rows, kv_packed, are not the "
                "plane's [L, page, Hkv, Dh]), and none is carried over it")
        params = params or SamplingParams()
        if kv_stream is not None:
            if k_pages is not None or v_pages is not None:
                raise ValueError("pass kv_stream alone, not with k_pages/v_pages")
            P, n_pages = int(kv_stream.page_size), int(kv_stream.n_pages)
        else:
            if not k_pages or not v_pages or len(k_pages) != len(v_pages):
                raise ValueError(
                    "submit_prefilled needs kv_stream, or k_pages and v_pages: "
                    "equal-length non-empty lists of [L, page_size, Hkv, Dh] "
                    "pages")
            P, n_pages = k_pages[0].shape[1], len(k_pages)
            if any(p.shape[1] != P for p in list(k_pages) + list(v_pages)):
                raise ValueError("transferred pages have mixed page sizes")
        if P != self.page_size:
            raise ValueError(
                f"transferred page size {P} != engine page_size "
                f"{self.page_size}: prefill and decode pools must agree")
        bucket = n_pages * P
        if bucket > self.max_len:
            raise ValueError(
                f"transferred prefix bucket {bucket} exceeds engine "
                f"max_len {self.max_len}")
        need = self._pages_needed(int(length), bucket, params.max_tokens)
        if need > self.num_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.num_pages - 1}")
        if int(length) + params.max_tokens > self.max_len:
            raise ValueError(
                f"prefix length {int(length)} + max_tokens {params.max_tokens} "
                f"does not fit engine max_len {self.max_len}")
        req = _Request(next(self._rid), [], params,
                       deadline_ts=float(deadline_ts or 0.0))
        req.submitted_ts = time.time()
        self._note_first_request(req.submitted_ts)
        if kv_stream is not None:
            req.kv_stream = kv_stream
            req.kv_pack = {"length": int(length),
                           "first_token": int(first_token)}
            # feed()/finish()/fail() wake the scheduler so a parked loop
            # adopts new pages immediately instead of on its poll tick
            kv_stream._wake = self._work.set
        else:
            req.kv_pack = {"k_pages": list(k_pages), "v_pages": list(v_pages),
                           "length": int(length),
                           "first_token": int(first_token)}
        req.generated = 1  # the transferred first token counts
        self._waiting.put(req)
        self._work.set()
        return req

    def generate(self, token_ids: list, params: SamplingParams | None = None,
                 *, lora: str | None = None) -> list:
        """Blocking: returns the generated token ids."""
        return list(self.stream(token_ids, params, lora=lora))

    def stream(self, token_ids: list, params: SamplingParams | None = None,
               *, lora: str | None = None):
        """Yields token ids as they are produced."""
        req = self.submit(token_ids, params, lora=lora)
        yield from _iter_request(req)

    def abort_request(self, rid: int) -> None:
        """Cancel an in-flight request by rid: the scheduler reclaims its
        decode slot and every granted KV page at the top of its next pass
        (within two decode steps: one may be in flight; not at max_tokens),
        and the caller's iterator raises RequestCancelledError. Thread-safe;
        a rid that already finished (or never existed) is a no-op that ages
        out."""
        self._abort_q.put(int(rid))
        self._work.set()

    def shutdown(self):
        self._stop = True
        self._work.set()
        self._thread.join(timeout=5.0)
        self._drain_all(None)

    def _drain_all(self, error: BaseException | None):
        """Unblock every waiting caller: end-of-stream, or the failure."""
        marker = _EngineError(error) if error is not None else _SENTINEL
        # rows released by count wait for their last tokens among the unread
        for req in list(self._by_slot.values()) + [
                req for u in list(self._unread) for _, req in u.rows]:
            if not req.finished:
                req.finished = True
                self._lora_release(req)
                req.out_queue.put(marker)
        for reqs in (self._backlog, self._prefilling, self._streaming):
            for req in reqs:
                self._lora_release(req)
                req.out_queue.put(marker)
            reqs.clear()
        while True:
            try:
                r = self._waiting.get_nowait()
                self._lora_release(r)
                r.out_queue.put(marker)
            except queue.Empty:
                break

    # ------------------------------------------------------------- scheduler

    def _bucket(self, n: int) -> int:
        return bucket_for(n, self.buckets[0], self.max_len)

    def _count_expert_tokens(self, n: int) -> None:
        """A program of `n` padded tokens was dispatched: the form its expert
        layers take (models/transformer.py `_moe_mlp`)."""
        moe = self.cfg.moe
        if moe is None:
            return
        if moe.dropless and ops.sorted_pays(n, moe.slots_a_held_expert(n)):
            self.expert_tokens_sorted += n
        else:
            self.expert_tokens_onehot += n
        if moe.share:
            layers = self.cfg.n_layers - self.cfg.n_dense_layers
            self.expert_slots_routed += n * moe.top_k * layers
            self.expert_calls += layers

    def _take_expert_counts(self, tree: dict) -> None:
        """A program's `expert_counts` (a prefill's kv, a decode step's state)
        leaves its tree for the next unread tokens to bring to the host."""
        counts = tree.pop("expert_counts", None)
        if counts is not None:
            counts.copy_to_host_async()
            self._expert_counts_unread.append(counts)

    def _pages_needed(self, prompt_len: int, bucket: int, max_tokens: int) -> int:
        """All pages this sequence will EVER touch, granted up front (no
        mid-flight allocation → no page-starvation deadlock): the prompt
        bucket plus generated positions up to prompt_len + max_tokens."""
        last_pos = min(prompt_len + max_tokens, self.max_len - 1)
        return max(bucket // self.page_size, last_pos // self.page_size + 1)

    # ---------------------------------------------------------- prefix cache

    def _block_hashes(self, tokens: list) -> list:
        """Chain hashes of the prompt's FULL page_size blocks: h_i commits
        to every token before the block too, so a hit means the whole
        prefix through block i is identical."""
        import hashlib

        out = []
        h = b""
        P = self.page_size
        for i in range(len(tokens) // P):
            blk = np.asarray(tokens[i * P:(i + 1) * P], np.int32).tobytes()
            h = hashlib.sha1(h + blk).digest()
            out.append(h)
        return out

    def _reclaimable_pages(self) -> int:
        # called from stats() on arbitrary threads while the scheduler
        # mutates the cache: snapshot first, tolerate a racing resize
        for _ in range(4):
            try:
                pages = list(self._prefix_cache.values())
                break
            except RuntimeError:
                continue
        else:
            return 0
        refs = self._page_refs
        return sum(1 for p in pages if refs.get(p, 0) == 0)

    def _available_pages(self) -> int:
        n = len(self._free_pages)
        if self.enable_prefix_cache:
            n += self._reclaimable_pages()
        return n

    def _grant_pages(self, need: int) -> list | None:
        """Take pages from the free list, evicting zero-ref cached blocks
        (LRU first) when the list runs short. None = infeasible now."""
        if need > self._available_pages():
            return None
        if need > len(self._free_pages):
            for h in list(self._prefix_cache):
                if len(self._free_pages) >= need:
                    break
                p = self._prefix_cache[h]
                if self._page_refs.get(p, 0) == 0:
                    del self._prefix_cache[h]
                    self._page_refs.pop(p, None)
                    self._page_hash.pop(p, None)
                    self._free_pages.append(p)
        return [self._free_pages.pop() for _ in range(need)]

    def _match_prefix(self, tokens: list, hashes: list) -> int:
        """Longest run of leading cached blocks usable for reuse. The block
        holding the LAST prompt token is never reused — at least one real
        token must go through prefill to produce the sampling logits."""
        usable = (len(tokens) - 1) // self.page_size
        n_pre = 0
        for i in range(min(usable, len(hashes))):
            p = self._prefix_cache.get(hashes[i])
            if p is None:
                break
            self._prefix_cache.move_to_end(hashes[i])  # LRU touch
            n_pre += 1
        return n_pre

    def _register_blocks(self, req: _Request) -> None:
        """Make this request's freshly-computed full blocks available to
        future prompts: their pages move from private (freed on release)
        to shared (ref-counted, cached)."""
        shared, priv = self._slot_shared[req.slot], self._slot_pages[req.slot]
        n_pre = len(shared)
        still_private = list(priv)
        for i in range(n_pre, len(req.tokens) // self.page_size):
            if req.pf_hashes[i] in self._prefix_cache:
                continue  # someone registered it first; keep ours private
            page = priv[i - n_pre]
            self._prefix_cache[req.pf_hashes[i]] = page
            self._page_hash[page] = req.pf_hashes[i]
            self._page_refs[page] = self._page_refs.get(page, 0) + 1
            shared.append(page)
            still_private.remove(page)
        self._slot_pages[req.slot] = still_private

    def _release_shared(self, slot: int) -> None:
        for p in self._slot_shared.pop(slot, ()):
            left = self._page_refs.get(p, 0) - 1
            if left <= 0:
                self._page_refs[p] = 0  # reclaimable; stays cached until
                # eviction needs the page (or a new request re-refs it)
            else:
                self._page_refs[p] = left

    def _set_row_sampling(self, slot: int, params: SamplingParams):
        with self._clock.dispatch("bind"):
            self._temps = self._temps.at[slot].set(params.temperature)
            self._topks = self._topks.at[slot].set(params.top_k)
        if params.guided is not None:
            self._guided_fsm[slot] = params.guided
            # the first token was already sampled under the START state's
            # mask (prefill path); its state advance happens in _emit
            self._guided_state[slot] = params.guided.start

    def _sample_first(self, req: _Request, logits, sub):
        """First-token sampling after a prefill, honoring the request's
        guided FSM start state (decode steps apply per-slot biases): the
        token as the unread tokens keep it ([1]) and as an insert takes it."""
        dispatch = self._clock.dispatch
        if req.params.guided is not None:
            from ray_tpu.llm import guided as _g

            bias = _g.bias_row(req.params.guided, req.params.guided.start,
                               remaining=req.params.max_tokens)
            with dispatch("bias"):
                logits = logits + jnp.asarray(bias)
        with dispatch("sample_first"):
            first = decoding.sample(logits[None, :], sub,
                                    req.params.temperature, req.params.top_k)
            return first, first[0]

    def _grant_ring(self, slot: int, row_pages: int) -> None:
        """The row's pages of the window pool, once, for its life: a ring of
        `self.ring`, or a page for every page the row can reach if fewer.
        That pool never runs out before the full one: it holds a ring for
        every slot, or as many pages as the full pool, and a row never takes
        more window pages than full ones."""
        if self.ring:
            self._slot_wpages[slot] = [self._free_wpages.pop()
                                       for _ in range(min(self.ring, row_pages))]

    def _return_pages(self, slot: int) -> None:
        """The slot's private pages of both pools back to their free lists."""
        self._free_pages.extend(self._slot_pages.pop(slot, ()))
        self._free_wpages.extend(self._slot_wpages.pop(slot, ()))

    def _ring_row(self, slot: int):
        """The slot's ring as the device takes it: [ring] ids, 0-padded
        (None for a model without window layers)."""
        if not self.ring:
            return None
        row = np.zeros((self.ring,), np.int32)
        held = self._slot_wpages[slot]
        row[:len(held)] = held
        with self._clock.dispatch("h2d"):
            return jnp.asarray(row)

    def _count_live(self, req: _Request, sign: int) -> None:
        """A row joins (+1) or leaves (-1) the rows the decode step advances:
        its positions, and those of them beyond the window, in the running
        sums the cache counters add every pass (`_dispatch_step` moves them a
        token at a time in between); and, if it samples, what it asks of the
        sampler, from which the step's form is chosen anew."""
        held = req.length0 + req.dispatched
        self._live_tokens += sign * held
        if self.cfg.window:
            self._live_beyond_window += sign * max(0, held - self.cfg.window)
        if req.params.temperature > 0:
            self._live_sampling += sign
            if req.params.top_k > 0:
                self._live_top_ks[req.params.top_k] += sign
                self._live_top_ks = +self._live_top_ks  # values somebody holds
            k_bucket = decoding.top_k_bucket(
                max(self._live_top_ks, default=0), self.cfg.vocab_size)
            self._sampler_form = (
                (False, 0, "steps_argmax") if not self._live_sampling else
                (True, k_bucket,
                 "steps_top_k" if k_bucket else "steps_categorical"))

    def _bind_slot(self, req: _Request, slot: int, length: int) -> None:
        """The slot-activation bookkeeping shared by every admission path:
        device sampling params, LoRA row, request registry. `length` is
        the row's device length at activation — mirrored host-side so the
        ragged decode step can bound its page sweep without a readback."""
        req.length0 = int(length)
        req.dispatched = 1  # the prefill's token, or the one transferred
        self._count_live(req, +1)
        self._set_row_sampling(slot, req.params)
        if self.lora_bank is not None:
            with self._clock.dispatch("bind"):
                self._slot_lora = self._slot_lora.at[slot].set(req.lora_idx)
        self._by_slot[slot] = req
        req.admitted_ts = time.time()
        if self._phase_admit is not None and req.submitted_ts:
            # decode-slot admission wait: submit → slot bind, covering the
            # waiting queue, page-pressure backlog, the prefill up to the
            # bind (a prefilled row is bound before its first token is
            # fetched) and (PD) the page pull
            self._phase_admit.observe(req.admitted_ts - req.submitted_ts)
        if req.kv_pack is not None:
            # prefilled elsewhere: the transferred first token is here
            self._scheduled(req, req.admitted_ts)
            self._first_token(req, req.admitted_ts)

    def _scheduled(self, req: _Request, now: float | None = None) -> None:
        """Slot and pages are granted and the prefill is about to be
        dispatched or staged: the request's queue wait ends. Once per
        request (a streamed PD request is scheduled when its pages are
        granted, long before the bind that brings its first token)."""
        if req.scheduled_ts:
            return
        req.scheduled_ts = now or time.time()
        self.requests_scheduled += 1
        if req.submitted_ts:
            wait = req.scheduled_ts - req.submitted_ts
            self.queue_wait_s += wait
            if self._phase_queue is not None:
                self._phase_queue.observe(wait)

    def _first_token(self, req: _Request, now: float | None = None) -> None:
        """The request's first token is on the host: its prefill ends."""
        req.first_token_ts = now or time.time()
        self.first_tokens += 1
        if req.scheduled_ts:
            took = req.first_token_ts - req.scheduled_ts
            self.prefill_s += took
            if self._phase_prefill is not None:
                self._phase_prefill.observe(took)

    # ------------------------------------- prefilled elsewhere (PD planes)

    def _grant_transferred(self, req: _Request, slot: int, n_pages: int) -> bool:
        """Every page a sequence transferred in `n_pages` will EVER need,
        granted up front like any admission. False when the pool can't host
        the sequence right now (caller backlogs)."""
        pages = self._grant_pages(self._pages_needed(
            req.kv_pack["length"], n_pages * self.page_size, req.params.max_tokens))
        if pages is None:
            return False
        self._slot_pages[slot] = pages
        return True

    def _activate_transferred(self, req: _Request, program: str) -> None:
        """The row's pages hold its transferred prefix: take it live."""
        pack = req.kv_pack
        with self._clock.dispatch(program):
            self.state = dp.activate_slot(
                self.state, req.slot, jnp.asarray(self._granted_block_row(req.slot)),
                jnp.int32(pack["length"]), jnp.asarray(pack["first_token"], jnp.int32))
        self._bind_slot(req, req.slot, pack["length"])

    def _insert_pages(self, req: _Request, slot: int) -> bool:
        """PD admission of pages that arrived whole from a prefill server:
        adopt them directly into the paged pool, one write_kv_pages scatter
        per page (a single [L, P, Hkv, Dh] compile serves every transfer),
        then activate the row. The whole-bucket [L, T, Hkv, Dh] array is
        never materialized on this path."""
        pack = req.kv_pack
        if not self._grant_transferred(req, slot, len(pack["k_pages"])):
            return False
        dt = self.state["kp"].dtype
        # prefix pages land in block-table order; the tail of the grant
        # hosts the generation
        dispatch = self._clock.dispatch
        for pid, kp, vp in zip(self._slot_pages[slot], pack["k_pages"], pack["v_pages"]):
            with dispatch("h2d"):
                kv = {"k": jnp.asarray(np.asarray(kp), dt),
                      "v": jnp.asarray(np.asarray(vp), dt)}
                ids = jnp.asarray(np.asarray([pid], np.int32))
            with dispatch("write_pages"):
                self.state = dp.write_kv_pages(self.state, kv, ids)
        self._note_prefill(0)
        self._activate_transferred(req, "activate")
        return True

    def _admit_stream(self, req: _Request, slot: int) -> bool:
        """Streamed PD admission (overlap transfer with decode): grant the
        slot and every page now; pages are written into the pool as the
        transfer plane delivers them (_drain_streams) and the row activates
        on the LAST page, the decode loop stepping other slots in between.
        While backlogged, arrived pages buffer host-side in the stream."""
        if not self._grant_transferred(req, slot, req.kv_stream.n_pages):
            return False
        self._scheduled(req)
        self._streaming.append(req)
        return True

    def _granted_block_row(self, slot: int) -> np.ndarray:
        """Zero-padded block-table row over the slot's pages, the cached
        prefix's shared ones first — the activation layout of every
        admission."""
        granted = self._slot_shared.get(slot, []) + self._slot_pages[slot]
        row = np.zeros((self.max_pages_per_seq,), np.int32)
        row[:len(granted)] = granted
        return row

    def _fail_stream(self, req: _Request, err) -> None:
        """Reclaim a streamed admission whose transfer died: the slot was
        granted but never activated, so only host bookkeeping unwinds —
        a per-REQUEST error; every other request keeps serving."""
        if req in self._streaming:
            self._streaming.remove(req)
        self._return_pages(req.slot)
        self._free.append(req.slot)
        self._lora_release(req)
        if not isinstance(err, BaseException):
            err = RuntimeError(str(err))
        req.out_queue.put(_RequestError(err))

    def _drain_streams(self) -> bool:
        """Adopt every page that arrived since the last scheduler pass:
        page-granular write_kv_pages into the slot's granted pages, slot
        activation once all pages landed. Runs between decode steps, so
        running requests keep emitting while transfers stream in."""
        progressed = False
        dispatch = self._clock.dispatch
        for req in list(self._streaming):
            st = req.kv_stream
            err = st.take_error()
            if err is not None:
                self._fail_stream(req, err)
                progressed = True
                continue
            try:
                ready = st.take_ready()
                if ready:
                    progressed = True
                    ready.sort(key=lambda t: t[0])
                    dt = self.state["kp"].dtype
                    if req.pf_done == 0 and len(ready) == st.n_pages:
                        # the whole transfer beat the scheduler here (fast
                        # sender / short prompt — the common case): write
                        # + activate in the ONE dispatch the non-streamed
                        # admission pays, instead of write_kv_pages +
                        # activate_slot
                        kcat, vcat = (np.concatenate(
                            [np.asarray(t[i]) for t in ready], axis=1)
                            for i in (1, 2))
                        with dispatch("h2d"):
                            kv = {"k": jnp.asarray(kcat, dt),
                                  "v": jnp.asarray(vcat, dt)}
                        length = req.kv_pack["length"]
                        with dispatch("adopt"):
                            self.state = dp.insert_sequence_paged(
                                self.state, req.slot, kv, jnp.int32(length),
                                jnp.asarray(req.kv_pack["first_token"],
                                            jnp.int32),
                                jnp.asarray(self._granted_block_row(req.slot)),
                                self.cfg)
                        self._note_prefill(0)
                        self._streaming.remove(req)
                        self._bind_slot(req, req.slot, length)
                        continue
                    pages = self._slot_pages[req.slot]
                    # consecutive arrivals collapse into ONE scatter
                    # per run (pages stream in order, so a whole
                    # prefetch window is usually one write); run
                    # lengths are bounded by the prefetch depth, so
                    # compile count stays small
                    runs: list = []
                    for i, kp, vp in ready:
                        if runs and runs[-1][0] + len(runs[-1][1]) == i:
                            runs[-1][1].append(kp)
                            runs[-1][2].append(vp)
                        else:
                            runs.append((i, [kp], [vp]))
                    for start, kps, vps in runs:
                        ids = pages[start:start + len(kps)]
                        kcat = np.concatenate(
                            [np.asarray(p) for p in kps], axis=1)
                        vcat = np.concatenate(
                            [np.asarray(p) for p in vps], axis=1)
                        with dispatch("h2d"):
                            kv = {"k": jnp.asarray(kcat, dt),
                                  "v": jnp.asarray(vcat, dt)}
                            ids = jnp.asarray(np.asarray(ids, np.int32))
                        with dispatch("adopt"):
                            self.state = dp.write_kv_pages(self.state, kv, ids)
                        self._note_prefill(0)
                        req.pf_done += len(kps)
                if req.pf_done >= st.n_pages:
                    self._streaming.remove(req)
                    self._activate_transferred(req, "adopt")
                    progressed = True
            except Exception as e:  # noqa: BLE001 — a malformed page must
                # fail THIS request, not the scheduler (engine death would
                # drop every other in-flight request)
                self._fail_stream(req, e)
                progressed = True
        return progressed

    def _pages_bound(self) -> int:
        """Power-of-two bound on the batch's LIVE page span (host mirror
        of the device lengths): the ragged decode step sweeps only this
        many block-table columns, so attention FLOPs/HBM traffic track
        the longest RESIDENT row instead of max_len, and compile count
        stays O(log max_pages)."""
        P = self.page_size
        need = 1
        for req in self._by_slot.values():
            # DISPATCHED positions: a step in flight has moved the row on
            pos = req.length0 + req.dispatched - 1
            need = max(need, pos // P + 1)
        b = 1
        while b < need:
            b *= 2
        return min(b, self.max_pages_per_seq)

    def _next_waiting(self):
        if self._backlog:
            return self._backlog.pop(0)
        try:
            return self._waiting.get_nowait()
        except queue.Empty:
            return None

    def _admit(self):
        admitted = 0
        while self._free and admitted < self.max_prefills_per_step:
            req = self._next_waiting()
            if req is None:
                return
            if self._cancel_at_admission(req):
                continue
            slot = self._free.pop()
            req.slot = slot
            if req.kv_pack is None:
                granted = self._admit_prompt(req, slot)
            elif req.generated >= req.params.max_tokens:
                # budget already spent by the transferred first token
                self._free.append(slot)
                self._lora_release(req)
                req.out_queue.put(_SENTINEL)
                continue
            elif req.kv_stream is not None:
                granted = self._admit_stream(req, slot)
            else:
                granted = self._insert_pages(req, slot)
            if not granted:
                self._free.append(slot)
                self._backlog.append(req)
                return  # page pressure: stop admitting this round
            # a streamed admission is pure bookkeeping (slot + pages granted
            # now, pages adopted as they arrive: _drain_streams), no prefill
            # compute, so it does NOT count against the per-step prefill
            # budget: a burst of transfers grabs every free slot in one round
            admitted += req.kv_stream is None

    def _admit_prompt(self, req: _Request, slot: int) -> bool:
        """The one admission of a prompt prefilled here: match a cached
        prefix (prefix cache on), grant every page the row will ever touch
        BEFORE any device work, then stage the prompt for chunked prefill or
        run its one span and take the row live. False when the pool can't
        host the sequence now (caller backlogs: nothing was dispatched)."""
        tokens = req.tokens
        n = len(tokens)
        P = self.page_size
        n_pre = 0
        if self.enable_prefix_cache:
            if req.pf_hashes is None:  # not a retry out of the backlog
                req.pf_hashes = self._block_hashes(tokens)
            n_pre = self._match_prefix(tokens, req.pf_hashes)
            # shrink the reused prefix if suffix-bucket roundup would
            # overflow the static block table
            while n_pre > 0 and (n_pre + self._bucket(n - n_pre * P) // P
                                 > self.max_pages_per_seq):
                n_pre -= 1
        pre_len = n_pre * P
        suffix = tokens[pre_len:]
        # behind no prefix this is the prompt's own bucket: the count that
        # submit() checked against the pool
        total_pages = self._pages_needed(
            n, pre_len + self._bucket(len(suffix)), req.params.max_tokens)
        chunk = self.prefill_chunk
        staged = chunk is not None and len(suffix) > chunk
        if staged:
            # long admission: stage for chunk-at-a-time prefill interleaved
            # with decode steps. Page need accounts for per-chunk bucket
            # spans (the final partial chunk pads to its own bucket). The
            # inflated count is committed ONLY if staging goes ahead — the
            # whole-prompt fallback must keep its own (table-fitting) need.
            rem = len(suffix) % chunk
            span = pre_len + (len(suffix) - rem) + (self._bucket(rem) if rem else 0)
            if max(span // P, total_pages) > self.max_pages_per_seq:
                staged = False  # bucket roundup overflow: whole-prompt path
            else:
                total_pages = max(span // P, total_pages)
        # pin the matched pages BEFORE granting: the grant evicts zero-ref
        # cached blocks, and the ones just matched must not be among them
        pre_pages = [self._prefix_cache[h] for h in (req.pf_hashes or ())[:n_pre]]
        for p in pre_pages:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        priv = self._grant_pages(total_pages - n_pre)
        if priv is None:
            for p in pre_pages:  # unpin; the request is backlogged
                self._page_refs[p] = self._page_refs.get(p, 1) - 1
            return False
        self._slot_pages[slot] = priv
        self._grant_ring(slot, total_pages)
        self._slot_shared[slot] = pre_pages
        self._scheduled(req)
        req.prefix_reused = pre_len
        if self.enable_prefix_cache:
            if n_pre:
                self.prefix_hits += 1
                self.prefix_tokens_reused += pre_len
            else:
                self.prefix_misses += 1
        if staged:
            req.pf_done = pre_len
            req.pf_pages = pre_pages + priv
            self._staged_tokens += pre_len
            self._prefilling.append(req)
            return True  # no first token yet
        # a row with a ring has no cached prefix (the prefix cache is refused
        # over window layers), so this span never gathers a window
        logits, kv = self._run_prefill(req, suffix, pre_len, pre_pages)
        self._go_live(req, logits, kv, "admit_wait")
        return True

    def _run_prefill(self, req: _Request, tokens: list, done: int, pages: list,
                     ring=None, carried: dict | None = None) -> tuple:
        """Run `tokens`, a span of the request's prompt, behind the `done`
        tokens of it resident in the leading `pages` of its block table (a
        cached prefix, the chunks before): (logits, kv) of the span, padded
        to its bucket. `ring` is the row's window ring as the device takes
        it, `carried` what the span before left (a recurrent state)."""
        n = len(tokens)
        P = self.page_size
        bucket = self._bucket(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        self._count_expert_tokens(bucket)
        dispatch = self._clock.dispatch
        with dispatch("h2d"):
            padded = jnp.asarray(padded)
        if done == 0:
            with dispatch("prefill"):
                logits, kv = decoding.prefill(
                    self.params, padded, jnp.int32(n), self.cfg,
                    *(() if self.lora_bank is None
                      else (self.lora_bank, jnp.int32(req.lora_idx))))
        else:
            # pad the resident pages' id list to a power of two so compile
            # count stays O(log(max_pages) × buckets); tail ids point at
            # scratch page 0, masked out by prefix_len
            n_pre = done // P
            npad = 1
            while npad < n_pre:
                npad *= 2
            padded_ids = np.zeros((npad,), np.int32)
            padded_ids[:n_pre] = pages[:n_pre]
            with dispatch("h2d"):
                padded_ids = jnp.asarray(padded_ids)
            with dispatch("gather_prefix"):
                k_pre, v_pre = dp.gather_prefix_pages(
                    self.state["kp"], self.state.get("vp"), padded_ids)
            self.prefix_tokens_gathered += done
            window = ()
            if ring is not None:
                with dispatch("gather_window"):
                    window = dp.gather_window_pages(
                        self.state, ring, jnp.int32(done), self.cfg)
            self._note_continuation(done, npad * P, bucket, n)
            with dispatch("prefill_with_prefix"):
                logits, kv = dp.prefill_with_prefix(
                    self.params, padded, k_pre, v_pre, jnp.int32(done),
                    jnp.int32(n), self.cfg, *window, **(carried or {}),
                    kernel=self._ragged_kernel)
        self._take_expert_counts(kv)
        self._note_prefill(bucket)
        if self.cfg.ssm:
            Q, layers = self.cfg.ssm.chunk, self.cfg.n_ssm_layers
            ran = -(-bucket // Q) * Q
            self.scan_positions += layers * ran
            self.scan_padded += layers * (ran - n)
            if self._scan_kernel:
                self.scan_kernel_positions += layers * ran
            if self._mixer_kernel and ops.ssm.kda_mixer_tiles(
                    bucket, self.cfg.ssm.d_head, self.cfg.ssm.d_conv):
                self.mixer_kernel_positions += layers * ran
        return logits, kv

    def _go_live(self, req: _Request, logits, kv, wait: str) -> None:
        """The one way a prompt prefilled here becomes a decode row: sample
        its first token from the last span's `logits`, hand the device the
        row, bind the slot, publish its blocks (prefix cache on), and leave
        the token among the unread, its fetch timed as `wait`. Which program
        takes the row live is decided here alone: a staged row's pages were
        written chunk by chunk (`kv` None: `activate_slot`); an engine with
        neither prefix cache nor chunks inserts the whole bucket; any other
        inserts the span behind the block row's leading pages."""
        slot, n, P = req.slot, len(req.tokens), self.page_size
        dispatch = self._clock.dispatch
        with dispatch("split"):
            self.key, sub = jax.random.split(self.key)
        first, token = self._sample_first(req, logits, sub)
        block_row = self._granted_block_row(slot)
        ring = self._ring_row(slot)
        if kv is None:
            with dispatch("activate"):
                self.state = dp.activate_slot(
                    self.state, slot, jnp.asarray(block_row), jnp.int32(n),
                    token, ring, req.pf_state)
            req.pf_state = None
        elif not (self.enable_prefix_cache or self.prefill_chunk):
            with dispatch("insert"):
                self.state = dp.insert_sequence_paged(
                    self.state, slot, kv, jnp.int32(n), token,
                    jnp.asarray(block_row), self.cfg, ring)
        else:
            n_pre = len(self._slot_shared[slot])
            span_pages = np.asarray(
                self._slot_pages[slot][:self._bucket(n - n_pre * P) // P], np.int32)
            with dispatch("insert"):
                self.state = dp.insert_sequence_paged_prefix(
                    self.state, slot, kv, jnp.asarray(span_pages),
                    jnp.asarray(block_row), jnp.int32(n), token, self.cfg, ring)
        self._bind_slot(req, slot, n)
        if self.enable_prefix_cache:
            self._register_blocks(req)
        self._first_unread(req, first, wait)

    def _note_continuation(self, prefix_len: int, span: int, bucket: int,
                           length: int) -> None:
        """A continuation's dispatch on the record: `length` tokens in a
        bucket of `bucket` behind `prefix_len`, the full layers over a
        gathered span of `span`, the window layers over their window's
        worth. A query at position p sees the p + 1 keys up to itself, on a
        window layer the last `window` of them."""
        cfg = self.cfg
        n_window = cfg.n_layers - cfg.n_full_layers if cfg.window else 0
        keys = prefix_len + 1 + np.arange(length, dtype=np.int64)
        self.attended_pairs += (cfg.n_planes - n_window) * int(keys.sum())
        spans = [span]
        if n_window:
            self.attended_pairs += n_window * int(np.minimum(keys, cfg.window).sum())
            spans.append(cfg.window)
        took = all(dp.continuation_blocks(cfg, bucket, keys_held, self._ragged_kernel)
                   for keys_held in spans)
        self.continuations_kernel += took
        self.continuations_xla += not took

    def _prefill_step(self):
        """Run ONE chunk of the oldest staged prefill (called between
        decode steps, so running requests keep emitting during a long
        admission — reference capability: vLLM chunked prefill)."""
        req = self._prefilling[0]
        P = self.page_size
        done = req.pf_done
        chunk = req.tokens[done:done + self.prefill_chunk]
        bucket = self._bucket(len(chunk))
        # the window layers' part goes through the row's ring, whose slots
        # models/decoding_paged.py finds from the chunk's start; a recurrent
        # state rides from chunk to chunk with the request and enters its
        # slot when the row goes live
        ring = self._ring_row(req.slot)
        logits, kv = self._run_prefill(
            req, chunk, done, req.pf_pages, ring,
            None if req.pf_state is None else {"row_state": req.pf_state})
        if self.cfg.ssm:
            req.pf_state = {name: kv.pop(name) for name in ("ssm", "conv")}
        dispatch = self._clock.dispatch
        with dispatch("h2d"):
            chunk_pages = jnp.asarray(np.asarray(
                req.pf_pages[done // P:(done + bucket) // P], np.int32))
        with dispatch("write_pages"):
            self.state = dp.write_kv_pages(
                self.state, kv, chunk_pages,
                *(() if ring is None else (ring, jnp.int32(done))),
                dense_layers=self.cfg.n_dense_layers)
        req.pf_done = done + len(chunk)
        req.pf_chunks += 1
        self.prefill_chunks_run += 1
        self._staged_tokens += len(chunk)
        if req.pf_done < len(req.tokens):
            return
        self._prefilling.pop(0)
        self._staged_tokens -= len(req.tokens)
        self._go_live(req, logits, None, "prefill_wait")

    def _first_unread(self, req: _Request, first, wait: str) -> None:
        """A prefill's first token (`first` [1], on the device) joins the
        unread tokens: it is read after the decode step that follows its
        prefill has been dispatched, so neither that step nor the tokens of
        the step in flight wait for the prefill. A row it spends
        (`max_tokens` 1) is released before a decode step takes it."""
        first.copy_to_host_async()
        self._unread.append(_Unread(first, [(0, req)], wait))
        self._retire_if_spent(req)

    def _note_prefill(self, prompt_tokens: int) -> None:
        """The pass has put a prefill program (or transferred pages) on the
        device ahead of its decode step: that step is a `step_prefill`."""
        self._pass_prefill = (self._pass_prefill or 0) + prompt_tokens

    def _retire_if_spent(self, req: _Request) -> None:
        """A row whose last token has been dispatched takes part in no
        further step: ending by count needs no token, so its slot and pages
        go back now and its stream closes when the tokens are read."""
        if req.dispatched >= req.params.max_tokens:
            self._release_active(req)
            self._closing += 1

    def _emit(self, req: _Request, token_id: int):
        if self._phase_gap is not None:
            now = time.time()
            last = req.last_emit_ts or req.admitted_ts
            if last:
                self._phase_gap.observe(now - last)
            req.last_emit_ts = now
        req.generated += 1
        # still the slot's row: not released by count while this was unread
        # (the slot, and its FSM entry, may be another request's by now)
        bound = self._by_slot.get(req.slot) is req
        fsm = self._guided_fsm.get(req.slot) if bound else None
        if fsm is not None:
            self._guided_state[req.slot] = fsm.step(
                self._guided_state[req.slot], token_id)
        stops = set(req.params.stop_token_ids)
        eos = token_id in stops
        if not eos:
            req.out_queue.put(token_id)
        if eos or req.generated >= req.params.max_tokens:
            if bound:
                self._release_active(req)
            else:
                self._closing -= 1
            self._close(req, _SENTINEL)

    def _close(self, req: _Request, marker) -> None:
        """End the client's stream; what is still unread of it is dropped."""
        req.finished = True
        req.out_queue.put(marker)
        if req.trace_ctx is not None and req.admitted_ts:
            self._emit_request_spans(req, time.time())

    def _release_active(self, req: _Request) -> None:
        """Return an ACTIVE row's slot, pages, LoRA ref and guided-FSM
        state to their pools — the one release path shared by completion
        (by count at the dispatch of the last step, `_retire_if_spent`; by a
        stop token when it is read, `_emit`) and mid-stream abort
        (_abort_one). A step in flight may still hold the row: it runs
        before this release on the device, writes inside the row's own
        pages, and its token is dropped."""
        with self._clock.dispatch("release"):
            self.state = dp.release_slot_paged(self.state, req.slot)
            if self.lora_bank is not None:
                self._slot_lora = self._slot_lora.at[req.slot].set(0)
        self._return_pages(req.slot)
        self._count_live(req, -1)
        if self.enable_prefix_cache:
            self._release_shared(req.slot)
        self._lora_release(req)
        self._guided_fsm.pop(req.slot, None)
        self._guided_state.pop(req.slot, None)
        self._free.append(req.slot)
        del self._by_slot[req.slot]

    def _emit_request_spans(self, req: _Request, now: float) -> None:
        """A sampled request's engine phases, from its stamps, under the
        span that was active at submit()."""
        ctx = req.trace_ctx
        try:
            tracing.emit_span_for(ctx, "engine:queue_wait", req.submitted_ts,
                                  req.scheduled_ts, rid=req.rid)
            first = req.first_token_ts or now  # aborted before it was read
            tracing.emit_span_for(
                ctx, "engine:prefill", req.scheduled_ts, first,
                prompt_tokens=len(req.tokens), chunks=req.pf_chunks,
                prefix_tokens_reused=req.prefix_reused)
            tracing.emit_span_for(ctx, "engine:decode", first, now,
                                  tokens=req.generated)
        except Exception as e:  # pragma: no cover — spans must never kill
            # the scheduler (every in-flight request would die)
            logger.debug("engine span emit failed: %r", e)

    # -------------------------------------------------- cancellation plane

    def _count_cancel(self) -> None:
        self.aborts += 1
        try:
            from ray_tpu.serve import request_context as _rc

            _rc.count_cancellation("engine")
        except Exception as e:  # pragma: no cover — metrics must never
            # kill the scheduler (every in-flight request would die)
            logger.debug("cancellation metric failed: %r", e)

    def _abort_one(self, req: _Request, err: BaseException) -> bool:
        """Reclaim one request wherever it currently lives (active slot,
        streamed admission, staged chunked prefill, page-pressure backlog)
        and surface `err` to its caller. Scheduler thread only. Returns
        False when the request is in none of the searchable registries
        (still in _waiting, or already finished)."""
        if req.slot >= 0 and self._by_slot.get(req.slot) is req:
            self._release_active(req)
        elif req in self._streaming:
            # _fail_stream reclaims + puts its own _RequestError
            self._fail_stream(req, err)
            self._count_cancel()
            return True
        elif req in self._prefilling:
            self._prefilling.remove(req)
            self._staged_tokens -= req.pf_done
            self._return_pages(req.slot)
            self._release_shared(req.slot)
            self._free.append(req.slot)
            self._lora_release(req)
        elif req in self._backlog:
            self._backlog.remove(req)
            self._lora_release(req)
        else:
            return False
        self._close(req, _RequestError(err))
        self._count_cancel()
        return True

    def _apply_aborts(self) -> None:
        """Drain abort_request() rids and reclaim their rows. Rids not yet
        admitted stay pending so _admit cancels them at pop time; stale
        ones (request already finished) age out after 120 s."""
        now = time.monotonic()
        while True:
            try:
                self._abort_pending.setdefault(self._abort_q.get_nowait(),
                                               now)
            except queue.Empty:
                break
        if not self._abort_pending:
            return
        for req in (list(self._by_slot.values()) + list(self._streaming)
                    + list(self._prefilling) + list(self._backlog)):
            if req.rid in self._abort_pending and self._abort_one(
                    req, RequestCancelledError(
                        f"request {req.rid} cancelled")):
                del self._abort_pending[req.rid]
        for rid, t in list(self._abort_pending.items()):
            if now - t > 120.0:
                del self._abort_pending[rid]

    def _expire_deadlines(self) -> None:
        """Abort every admitted request whose deadline passed — between
        decode steps, so an expired row costs no step beyond the one that
        may be in flight. Requests still in _waiting are checked at
        admission instead."""
        now = time.time()
        for reqs in (self._by_slot.values(), self._streaming,
                     self._prefilling, self._backlog):
            for req in list(reqs):
                if req.deadline_ts and now > req.deadline_ts:
                    self._abort_one(req, DeadlineExceededError(
                        f"request {req.rid} deadline exceeded "
                        f"({now - req.deadline_ts:.3f}s past)"))
                    self._abort_pending.pop(req.rid, None)

    def _cancel_at_admission(self, req: _Request) -> bool:
        """Refuse a popped waiting-queue request that was cancelled or
        whose queue-wait already spent its deadline budget — before any
        prefill compute or page grant."""
        if self._abort_pending.pop(req.rid, None) is not None:
            err: BaseException = RequestCancelledError(
                f"request {req.rid} cancelled before admission")
        elif req.deadline_ts and time.time() > req.deadline_ts:
            err = DeadlineExceededError(
                f"request {req.rid} deadline expired during queue wait")
        else:
            return False
        self._lora_release(req)
        req.out_queue.put(_RequestError(err))
        self._count_cancel()
        return True

    def _loop(self):
        accelerators.note_thread_activity(lambda: self._clock.phase)
        try:
            # every program of a sharded engine is traced with its mesh
            # ambient: the flash prefill kernel shard_maps itself over it
            # (ops/attention.py), GSPMD cannot partition it
            with (jax.set_mesh(self.mesh) if self.mesh is not None
                  else contextlib.nullcontext()):
                self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — engine death must unblock callers
            self._error = e
            self._drain_all(e)
            raise

    def _loop_inner(self):
        """One loop, one decode step in flight: step N+1's programs go out
        before step N's tokens are read, so the device has its next step
        queued while the host fetches, emits, sweeps and admits. Nothing the
        device needs for a step comes from the host (the sampled token is
        committed on the device, a row's pages are all granted at admission);
        what the host learns a step late is a stop token, and that row-step's
        token is dropped (`_deliver`). While a guided row lives its next mask
        is built from the token before, and the same code reads the step it
        has just dispatched: depth 0 for depth 1."""
        mark = self._clock.mark
        while not self._stop:
            # cancellation + deadline sweep first: an aborted/expired row's
            # slot and pages are back in the pool before this pass admits
            # or steps anything (reclaim within two decode steps: the step
            # in flight still holds the row, and its token is dropped)
            mark("sweep")
            self._apply_aborts()
            self._expire_deadlines()
            if (not self._by_slot and not self._unread
                    and self._waiting.empty() and not self._backlog
                    and not self._prefilling and not self._streaming):
                mark("parked")
                self._work.wait(timeout=0.1)
                self._work.clear()
                continue
            mark("admit")
            self._admit()
            progress = False
            if self._streaming:
                mark("streams")
                progress = self._drain_streams()
            if self._prefilling:
                # one chunk per iteration: decode below keeps running
                # requests emitting while a long prompt streams in
                mark("prefill")
                self._prefill_step()
            if self._unread and (self._guided_fsm or not self._by_slot):
                # a guided row's mask needs every token it has been given,
                # and with no row to step there is nothing to run ahead of
                self._deliver(0)
                progress = True
            if not self._by_slot:
                if self._streaming and not progress:
                    # nothing decodable and no new pages yet: park until
                    # the transfer plane's feed() wakes us
                    mark("parked")
                    self._work.wait(timeout=0.005)
                    self._work.clear()
                continue
            self._dispatch_step()
            self._deliver(0 if self._guided_fsm else 1)

    def _dispatch_step(self) -> None:
        """One decode step's four programs go out and its tokens join the
        unread; every counter of the step is of what was DISPATCHED, a
        row-step whose token will be dropped included."""
        t_step = self._clock.mark("decode")
        dispatch = self._clock.dispatch
        rows = list(self._by_slot.items())
        pages_bound = self._pages_bound()
        with dispatch("decode_step"):
            state, logits = dp.decode_step_paged_ragged(
                self.params, self.state, self.cfg, pages_bound,
                self._ragged_kernel, self.lora_bank, self._slot_lora)
        # out of the state before the next program donates it (a gated stack)
        exit_cdf = state.pop("exit_cdf", None)
        self._take_expert_counts(state)
        with dispatch("split"):
            self.key, sub = jax.random.split(self.key)
        if self._guided_fsm:
            # per-slot FSM masks as an additive bias; the sampling math
            # itself stays in the one jitted sample_per_row program.
            # `remaining` triggers the budget-aware closing mask so an
            # unbounded pattern completes before max_tokens.
            from ray_tpu.llm import guided as _g

            bias = np.zeros(logits.shape, np.float32)
            for slot, fsm in self._guided_fsm.items():
                r = self._by_slot[slot]
                bias[slot] = _g.bias_row(
                    fsm, self._guided_state[slot],
                    remaining=r.params.max_tokens - r.generated)
            with dispatch("bias"):
                logits = logits + jnp.asarray(bias)
        # sampling params live on device, updated only at admission; the
        # sampler's form follows what the live rows ask for
        sampling, k_bucket, form = self._sampler_form
        with dispatch("sample"):
            toks = decoding.sample_per_row(logits, sub, self._temps,
                                           self._topks, sampling, k_bucket)
        with dispatch("commit"):
            self.state = decoding.commit_tokens(state, toks)
        toks.copy_to_host_async()
        if exit_cdf is not None:
            exit_cdf.copy_to_host_async()
        if any(u.step for u in self._unread):
            self.steps_ahead += 1
        prefill, self._pass_prefill = self._pass_prefill, None
        counts, self._expert_counts_unread = tuple(self._expert_counts_unread), []
        self._unread.append(_Unread(
            toks, rows, "decode_wait", t_step, exit_cdf,
            "step" if prefill is None else "step_prefill", prefill or 0, counts))
        self.decode_steps += 1
        self.stack_passes += self.cfg.n_passes
        self.sampler_steps[form] += 1
        self.decode_slot_steps += len(rows)
        self._count_expert_tokens(self.max_slots)
        # each live row attended over length0 + dispatched positions on
        # a full layer, no more than the window of them on a window layer
        self.context_tokens += self._live_tokens
        self.window_context_tokens += (self._live_tokens
                                       - self._live_beyond_window)
        self.page_steps_used += (self.num_pages - 1
                                 - self._available_pages())
        self.page_steps_total += self.num_pages - 1
        granted = self.num_pages - 1 - len(self._free_pages)
        wgranted = max(self.window_pages - 1, 0) - len(self._free_wpages)
        self.held_token_steps += self._live_tokens + self._staged_tokens
        self.held_byte_steps += (granted * self._page_bytes
                                 + wgranted * self._wpage_bytes)
        self.window_page_steps_used += wgranted
        self.window_page_steps_total += max(self.window_pages - 1, 0)
        if not self.cfg.mla:
            # the blocks of pages a full layer's launch walked for these rows,
            # through the table it was handed (ops/ragged_paged_attention.py)
            self.ragged_block_positions += walked_positions(
                np.array([req.length0 + req.dispatched - 1 for _, req in rows]),
                page_size=self.page_size, kv_heads=self.cfg.kv_row[0],
                head_dim=self.cfg.kv_row[1], itemsize=self.state["kp"].dtype.itemsize,
                table_pages=table_width(self.max_pages_per_seq, pages_bound,
                                        self.cfg.kv_row[1], self._ragged_kernel))
        for _, req in rows:
            req.dispatched += 1
            self._live_tokens += 1
            if self.cfg.window and req.length0 + req.dispatched > self.cfg.window:
                self._live_beyond_window += 1
            self._retire_if_spent(req)

    def _deliver(self, depth: int) -> None:
        """Read the unread tokens, oldest first, until `depth` are left (the
        step just dispatched, or none), and give each to the request that
        held its row at dispatch. A request that has ended since (a stop
        token in the step before, an abort) has its token dropped: never
        queued, never counted in `generated`."""
        mark = self._clock.mark
        while len(self._unread) > depth:
            u = self._unread.popleft()
            mark(u.wait)
            toks, exit_cdf, counts = jax.device_get((u.toks, u.exit_cdf, u.expert_counts))
            for c in counts:
                self.expert_counts += c
            now = mark("emit")
            if u.step:
                # one measurement, two sinks
                took = now - max(u.t_dispatch, self._t_fetched)
                self._clock.book_pass(u.kind, took, len(u.rows), u.prompt_tokens)
                if self._step_obs is not None:
                    self._step_obs[u.kind].observe(took)
                self._t_fetched = now
                if exit_cdf is not None:
                    np.add.at(self.exit_rows,
                              (exit_cdf[[slot for slot, _ in u.rows]] >= 0.5
                               ).argmax(axis=1), 1)
            for i, req in u.rows:
                if req.finished:
                    self.tokens_discarded += u.step
                    continue
                if not u.step:
                    self._first_token(req)
                self._emit(req, int(toks[i]))

    # ---------------------------------------------------------------- stats

    def stats(self) -> dict:
        memory = jax.local_devices()[0].memory_stats() or {}  # None on CPU
        out = {"free_slots": len(self._free),
               # rows in a slot, and rows released by count whose last
               # tokens are not read yet
               "active": len(self._by_slot) + self._closing,
               "waiting": self._waiting.qsize() + len(self._backlog),
               "streaming": len(self._streaming),
               "max_slots": self.max_slots, "buckets": list(self.buckets),
               # which decode-attention code runs: the Pallas kernel only on
               # an unsharded TPU engine, the pure-JAX reference elsewhere
               "decode_attn": self._decode_attn,
               "device": accelerators.device_report(),
               "device_memory": {
                   k: memory.get(k) for k in (
                       "bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
               "worker_chips": accelerators.current_worker_chips(),
               "compile_cache": accelerators.compile_cache_counts(),
               # the start by stage, process start to first request, with the
               # compile seconds as they stood at three of the stamps; None
               # for an engine that from_config did not build
               "setup": self._setup and {
                   **self._setup, "seconds": dict(self._setup["seconds"]),
                   "compile_at": dict(self._setup["compile_at"])},
               "decode_steps": self.decode_steps,
               # live rows summed over decode steps
               "decode_slot_steps": self.decode_slot_steps,
               # steps_ahead: decode steps dispatched while the step before
               # them was unread; tokens_discarded: row-steps whose token was
               # dropped because the row had stopped or been aborted
               "loop": {**self._clock.snapshot(), "requests": {
                   "requests_scheduled": self.requests_scheduled,
                   "queue_wait_s": self.queue_wait_s,
                   "first_tokens": self.first_tokens,
                   "prefill_s": self.prefill_s},
                   "steps_ahead": self.steps_ahead,
                   "tokens_discarded": self.tokens_discarded},
               "aborts": self.aborts,
               "decode_occupancy": (self.decode_slot_steps
                                    / self.decode_steps
                                    if self.decode_steps else 0.0),
               "free_pages": len(self._free_pages),
               "num_pages": self.num_pages, "page_size": self.page_size}
        out["cache"] = {
            # as stored, all layers (window layers too: what a token costs a
            # row that has not left the window): a latent row, or K and V
            # of every head
            "bytes_per_token": (self._page_bytes + self._wpage_bytes) // self.page_size,
            "context_tokens": self.context_tokens,
            "prefix_tokens_gathered": self.prefix_tokens_gathered,
            "page_steps_used": self.page_steps_used,
            "page_steps_total": self.page_steps_total,
            "held_token_steps": self.held_token_steps,
            "held_byte_steps": self.held_byte_steps}
        if not self.cfg.mla:
            out["cache"]["ragged_block_positions"] = self.ragged_block_positions
        if self.cfg.ssm:
            out["cache"]["state_bytes_per_row"] = self._state_bytes_per_row
        if self.ring:
            out["cache"].update(
                window_context_tokens=self.window_context_tokens,
                window_page_steps_used=self.window_page_steps_used,
                window_page_steps_total=self.window_page_steps_total)
            out["window_pages"] = self.window_pages
            out["free_window_pages"] = len(self._free_wpages)
            out["ring"] = self.ring
        # cache planes from the state's own shapes: a layer application each
        out["loops"] = {"passes": self.cfg.n_passes,
                        "planes": sum(self.state[k].shape[0] for k in ("kp", "wkp")
                                      if k in self.state),
                        "stack_passes": self.stack_passes}
        if self.cfg.exit_gate:
            out["loops"]["exit_rows"] = self.exit_rows.tolist()
        out["experts"] = {"tokens_sorted": self.expert_tokens_sorted,
                          "tokens_onehot": self.expert_tokens_onehot}
        if self.cfg.moe is not None and self.cfg.moe.share:
            # slots_routed and calls of what was dispatched; slots_held and
            # groups_with_rows of what has been read (a step behind)
            out["experts"].update(
                slots_routed=self.expert_slots_routed, calls=self.expert_calls,
                slots_held=int(self.expert_counts[0]),
                groups_with_rows=int(self.expert_counts[1]))
        # decode steps by the form the sampler took (they add up to
        # decode_steps): how often anything beyond an argmax is paid for
        out["sampler"] = dict(self.sampler_steps)
        out["prefill"] = {"continuations_kernel": self.continuations_kernel,
                          "continuations_xla": self.continuations_xla,
                          "attended_pairs": self.attended_pairs}
        if self.cfg.ssm:
            out["prefill"].update(scan_positions=self.scan_positions,
                                  scan_padded=self.scan_padded,
                                  scan_kernel_positions=self.scan_kernel_positions,
                                  mixer_kernel_positions=self.mixer_kernel_positions)
        if self.prefill_chunk:
            out["prefill_chunk"] = self.prefill_chunk
            out["prefill_chunks_run"] = self.prefill_chunks_run
            out["prefilling"] = len(self._prefilling)
        if self.enable_prefix_cache:
            hits, misses = self.prefix_hits, self.prefix_misses
            out["prefix_cache"] = {
                "hits": hits, "misses": misses,
                "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
                "tokens_reused": self.prefix_tokens_reused,
                "cached_blocks": len(self._prefix_cache),
                "reclaimable_pages": self._reclaimable_pages(),
            }
        return out
