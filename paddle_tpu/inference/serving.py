"""Continuous-batching decode engine over the paged-KV Pallas kernel.

The r5 kernel work (ops/pallas/paged_attention.py) gave single-token
decode over paged KV; what was missing is the ENGINE that serves a stream
of requests through it (the reference's serving stack around
block_multi_head_attention; vLLM's engine shape).  Three pieces:

- ``BlockManager`` (inference/kv_cache.py): a fixed page pool with
  per-sequence block tables — admission claims pages, decode grows them
  one page at a time, retirement/preemption returns them.  With prefix
  caching on (the default) the pool is content-addressed: admission
  matches each prompt's token chain against pages other sequences
  already computed, takes refcounted references on the hits, and only
  the MISS SUFFIX is allocated and prefilled.  Writes into a shared
  page copy it first (copy-on-write), and freed pages park in an LRU so
  a hot system prompt stays resident until the pool truly needs the
  space.

- A continuous-batching scheduler: every ``step()`` admits waiting
  requests into the running batch (no waiting for the batch to drain),
  retires sequences on eos/max-tokens, and — when the page pool is
  exhausted mid-decode — preempts the youngest sequence, returning its
  pages and requeuing it for recomputation (which now hits the prefix
  cache its own freed pages just populated).  Prefill is CHUNKED: each
  step packs at most ``max_prefill_tokens`` pending prompt tokens —
  partially-prefilled requests resume across steps at their absolute
  positions — so a long prompt never stalls running decodes; every
  step still runs one decode for the whole running set.

- ONE ragged compiled step program instead of per-phase programs
  (arxiv 2604.15464's serving shape): every step packs its whole mix —
  prefill chunks entering at absolute positions, resumed chunks,
  cache-hit suffixes, single decode tokens, and k-draft verify windows
  — as rows of flat query tokens described by ``(cu_seqlens, kv_lens,
  block_tables)``, padded to one token bucket.  Each layer writes the
  packed tokens' K/V into the paged cache, then one ragged
  paged-attention launch (ops/pallas/paged_attention.py) lets every
  token attend to its row's pages at its absolute position; a prefill
  chunk, a decode token, and a verify window differ only in their
  ``query_lens``.  On CPU the XLA dense-gather reference computes the
  same masked softmax (the oracle the byte-identity tests pin).  The
  caches thread through with buffer donation, so the
  [L, num_blocks, H_kv, bs, D] pool is updated in place on TPU instead
  of copied per step.

The decode math is term-for-term the math of ``_make_decode_fwd``
(models/llama.py), so greedy engine output is token-identical to
``LlamaForCausalLM.generate`` — with the prefix cache ON or OFF — and
tests/test_llm_engine.py + tests/test_prefix_cache.py hold the paths
together.

Speculative decoding (inference/spec_decode.py) rides the same cache
and the same program: a host-side ``Drafter`` proposes K tokens per
running sequence, the step packs each speculative sequence's
[last_token, d_1..d_k] window as one ragged row (the program returns
raw logits at every packed position alongside the sampled tokens), and
host-side rejection sampling accepts a prefix (greedy output stays
byte-identical to plain decode; sampled output follows the target
distribution exactly).  Rejected tokens roll back via
``BlockManager.truncate``.  Verify rows, prefill chunks, and
plain-decode rows share each step's single launch: per-request
``spec_k`` opts in, and a low acceptance rate auto-disables speculation
for that request.
"""
from __future__ import annotations

import contextlib
import functools
import math
import re
import time
from collections import deque
from dataclasses import dataclass, field
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import _rope_positions
from ..ops.pallas import mla_attention as _mla
from ..ops.pallas import paged_attention as _pa
from ..ops.pallas import quant_matmul as _qm
from ..ops.pallas import selective_scan as _scan
from ..profiler import ServingStats
from .faults import InjectedFault
from . import layer_stack as _ls
from .kv_cache import (NULL_BLOCK, BlockManager, BlockPoolExhausted,
                       prefix_chain_hashes)
from .policy import pack_prefill_chunks
from .pressure import STATE_NAMES as _TIER_NAMES
from .sampling import (advance_keys, greedy_tokens, make_samp,
                       samp_structs, sample_tokens)

__all__ = ["LLMEngine", "Request", "RequestOutput"]

# the attention kinds a served model's layers may have (the keys of
# ``layer_stack.ATTENTION``, spelled out: graft-lint reads this literal).
# An engine's attention-bearing program kinds are bounded by it, whatever
# its requests do (rule ``attention-program-budget``).
ATTENTION_KINDS = ("mla", "mla_select", "mla_window", "gqa", "gqa_nope",
                   "gqa_window", "gqa_gated", "gqa_gated_window", "diff",
                   "diff_window", "diff_cross")


@dataclass(eq=False)
class Request:
    """One generation request in the engine's queues.  Two requests are
    the same only if they are the same object (``eq=False``: identity
    comparison and hashing): nothing compares the fields of two, and
    the engine itself asks ``is``, never ``==``."""
    rid: int
    prompt: list                      # original prompt token ids
    max_new_tokens: int
    temperature: float
    eos_token_id: object              # int | None
    seed: int
    top_k: int = 0                    # 0 -> off
    top_p: float = 1.0                # 1.0 -> off
    repetition_penalty: float = 1.0   # 1.0 -> off
    spec_k: int = 0                   # max draft tokens per verify round
    # scheduler state
    tokens: list = field(default_factory=list)   # tokens to (re)prefill
    generated: list = field(default_factory=list)
    cached: int = 0                   # positions whose KV is in the pool
    inflight: int = 0                 # positions the launch in flight
                                      # writes for it (a chunk's tokens, 1
                                      # for a decode row): what ``cached``
                                      # will have grown by at its commit
    arrival: int = 0                  # admission priority (FCFS)
    slot: int = -1                    # stable decode-batch slot: >= 0
                                      # exactly while the request is in
                                      # the running set (``_is_running``)
    t_arrival: float = 0.0            # wall clock at add_request (TTFT)
    seen: object = None               # [V] bool penalty mask (lazy)
    spec_proposed: int = 0            # drafts sent to verify (lifetime)
    spec_accepted: int = 0            # drafts accepted (lifetime)
    spec_disabled: bool = False       # acceptance fell below the floor
    tier_checked: int = -1            # spill-tier generation last consulted
    # streaming hooks (called from the engine's stepping thread): the
    # two per-request callbacks, or ONE launch-level sink that takes
    # everything a commit emitted in one call (``LLMEngine._emit``)
    on_token: object = None           # callable(rid, token) per emission
    on_finish: object = None          # callable(RequestOutput) at the end
    sink: object = None               # callable([(rid, tokens, output)])


@dataclass
class RequestOutput:
    rid: int
    prompt: list
    generated: list                   # includes the eos token when hit
    finish_reason: str                # "eos" | "length" | abort reason
                                      # ("aborted", "deadline", ...)

    @property
    def token_ids(self):
        return list(self.prompt) + list(self.generated)


@dataclass
class _StepTicket:
    """One dispatched-but-not-completed ragged launch.

    ``dispatch()`` fills it with the launch's UNMATERIALIZED device
    arrays plus the packed-row layout needed to apply them; ``complete()``
    blocks on the arrays and commits.  Between two ``step()`` calls at
    most one ticket is in flight.  Inside a call there may be two: the
    next launch is dispatched AHEAD of this one's commit, taking the
    token a decode row feeds in from ``sampled`` on the device (the step
    program's ``prev``/``src``), and this ticket is completed after."""
    chunks: list
    spec: list
    batch: list
    sampled: object                   # device array the host never reads:
                                      # the next launch's ``prev`` (None
                                      # of a decode-window launch)
    packed: object                    # device array, on its way to the
                                      # host since the launch: all the
                                      # host reads of it (_pack_results)
    logits: object                    # device array | None
    spec_slices: list
    chunk_slots: list
    batch_slots: list
    dispatch_s: float                 # host seconds packing + launching
    t_launch: float                   # perf_counter at launch return
    launch_ns: int                    # tracer clock at launch (0 untraced)
    step: int = 0                     # id of this launch: engine.launches
                                      # when it was dispatched
    inflight: bool = False            # crossed a step() boundary in flight
    window: int = 0                   # K of a decode-window launch (0 =
                                      # per-step; the packed grids are
                                      # [K, B])
    sample_chain: int = 0             # 1 if a window launch held a sampled
                                      # row (its drain counts the passes)
    slot_of: dict = field(default_factory=dict)   # rid -> logit row, of
                                      # every chunk and decode row aboard
    dropped: dict = field(default_factory=dict)   # rid -> "free" |
                                      # "release": rows the commit of the
                                      # launch BEFORE this one retired
                                      # while this one already held them;
                                      # they are dropped unapplied and
                                      # their pages given back only now


class _DecodeBufs:
    """One set of persistent host-side pack buffers for the pure-decode
    fast path.  With overlap on the engine holds TWO and alternates
    launches between them: CPU PJRT may zero-copy alias an aligned host
    array into the program's input, so the buffers of an in-flight
    launch must not be rewritten until its results materialize (and a
    launch dispatched ahead is packed while the one before it flies).

    ``bt_ver`` maps rid -> the block-table version staged into THIS
    buffer's ``bt`` row (the per-buffer replacement for the old
    per-request ``bt_version`` field: each buffer tracks its own
    staleness).  ``layout`` is the rid order last packed."""

    __slots__ = ("toks", "src", "cu", "kvl", "slot", "bt", "samp",
                 "layout", "bt_ver")

    def __init__(self, B, bt_shape, Lq, vocab_size):
        self.toks = np.zeros((B,), np.int32)
        # -1: the token is ``toks``'; else the logit row of the launch in
        # flight whose sample it is (the step program's ``src``)
        self.src = np.full((B,), -1, np.int32)
        self.cu = np.zeros((B + 1,), np.int32)
        self.kvl = np.zeros((B,), np.int32)
        # each row's batch slot (where a state-space model keeps its
        # state); B, the slot nobody holds, for a row of no request
        self.slot = np.full((B,), B, np.int32)
        # [B + 1, nblk], or [2, B + 1, nblk] where window layers have a
        # table of their own (LLMEngine._bt_shape)
        self.bt = np.full(bt_shape, NULL_BLOCK, np.int32)
        self.samp = make_samp(Lq, vocab_size)
        self.layout: tuple = ()
        self.bt_ver: dict = {}


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _sample_chain(samp) -> int:
    """1 if a launch's host-side ``samp`` holds a sampled row: the
    predicate ``sampling.sample_tokens`` branches on, on the device."""
    return int((samp["temps"] > 0.0).any())


def _named(fn, name: str):
    """``fn`` under a stable ``__name__``: ``jax.jit`` names the module
    after it (``jit_ragged_step_t192``), and a device trace names each
    execution after the module."""
    fn.__name__ = fn.__qualname__ = name
    return fn


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(?:\([^=]*?\)|\S+)\s+"
    r"([\w\-]+)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _instruction_scopes(hlo_text: str) -> dict:
    """{instruction name: {"op_name", "dot"}} of one compiled module's
    text: ``op_name`` is the instruction's metadata path, which holds
    the ``jax.named_scope`` names ("jit(ragged_step_t32)/layers/while/
    body/qkv/dot_general"; empty where XLA made the instruction up),
    ``dot`` whether it is a matrix product or a fusion that holds one.
    A device trace names each operation by its instruction's name."""
    out, calls, holds_dot = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            holds_dot[comp] = False
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, opcode = m.group(1), m.group(2)
        op = _HLO_OP_NAME.search(line)
        dot = opcode in ("dot", "convolution")
        holds_dot[comp] = holds_dot[comp] or dot
        out[name] = {"op_name": op.group(1) if op else "", "dot": dot}
        c = _HLO_CALLS.search(line)
        if c and opcode == "fusion":
            calls[name] = c.group(1)
    for name, called in calls.items():
        out[name]["dot"] = holds_dot.get(called, False)
    return out


def _refuse(what: str, needs: dict, asked: dict) -> None:
    """Raise by name for the first option of ``asked`` that is not at
    the one value ``needs`` supports: {name: (supported, why)}."""
    for name, (supported, why) in needs.items():
        if asked[name] != supported:
            raise ValueError(
                f"{name}={asked[name]!r} is not supported for a model "
                f"with {what}: {why}")


def _refuse_window_options(**asked) -> None:
    """A model that mixes global and sliding-window layers is served
    from float pages in two pools under two block tables and float
    weights on one chip, a step a launch, nothing shared between
    sequences.  Each option below needs what its message says before it
    can be taken: nothing has run it over the two tables.  A stack of
    latent AND window layers (``models/dots3.py``) is refused by this
    table and by ``_refuse_latent_options`` both, the latent one
    first."""
    _refuse("sliding-window layers", {
        "kv_dtype": ("float32", "int8 pages need scale pools for the "
                     "window layers' pool and their reset when a page "
                     "comes back from another sequence's window"),
        "weight_dtype": ("float32", "int8/int4 weights need quantized "
                         "expert pools and a grouped dequant product"),
        "tp": (1, "tp > 1 needs both pairs of pools and the experts laid "
               "over a mesh"),
        "drafter": (None, "a drafter needs verify rows rolled back in "
                    "the window layers' page lists too"),
        "decode_window": (1, "decode_window > 1 needs the window layers' "
                          "pages advanced inside the device loop"),
        "kv_tier": (None, "kv_tier needs the spill and restore of both "
                    "pools' pages"),
        "enable_prefix_caching": (False, "a prefix hit needs the window "
                                  "layers' pages of the prefix's last "
                                  "window of tokens, which their owner "
                                  "gave back as it moved on (and "
                                  "copy-on-write over both pools)"),
    }, asked)


def _refuse_latent_options(**asked) -> None:
    """A model with latent-attention layers is served from float pages
    and float weights on one chip, a step a launch.  Each option below
    needs what its message says before it can be taken."""
    needs = {
        "kv_dtype": ("float32", "int8 latent pages need a quantising "
                     "write and a dequantising load in the latent kernel "
                     "(and an indexer's keys quantised beside them, with "
                     "the same in its score kernel)"),
        "weight_dtype": ("float32", "int8/int4 weights need quantized "
                         "expert pools and a grouped dequant product"),
        "tp": (1, "tp > 1 needs the heads of the absorbed query and the "
               "experts laid over a mesh, with their exchange"),
        "drafter": (None, "a drafter needs verify rows' logits from the "
                    "latent step program"),
        "decode_window": (1, "decode_window > 1 needs a test and a cell: "
                          "the window loops the layer-stack forward, and "
                          "nothing has run it over the latent pool"),
        "kv_tier": (None, "kv_tier needs the spill and restore of latent "
                    "pages"),
    }
    _refuse("latent-attention (MLA) layers", needs, asked)


def _refuse_state_options(**asked) -> None:
    """A model with state-space layers keeps, beside its pages, a state
    a SEQUENCE a layer (the convolution's last inputs and the scan's
    state), by batch slot: written by every launch, read by the next.
    Each option below needs what its message says before it can be
    taken: nothing has run it over a state.  Such a model has window
    layers too and is refused by ``_refuse_window_options`` as well,
    this table first."""
    _refuse("state-space layers", {
        "enable_prefix_caching": (False, "a prefix hit needs a snapshot "
                                  "of the state at the prefix's end (at "
                                  "page boundaries, beside the pages)"),
        "drafter": (None, "a drafter needs the state rolled back to the "
                    "last accepted row of a verify window"),
        "decode_window": (1, "decode_window > 1 needs the state carried "
                          "through the device loop's turns"),
        "kv_tier": (None, "kv_tier needs the spill and restore of a "
                    "sequence's state with its pages"),
        "kv_dtype": ("float32", "int8 pages need scale pools beside the "
                     "two page pools, and nothing has run them beside a "
                     "state"),
        "weight_dtype": ("float32", "int8/int4 weights need a dequant "
                         "product for the scan's projections"),
        "tp": (1, "tp > 1 needs the state's d_inner and the pages' heads "
               "laid over a mesh"),
    }, asked)


# what wraps a launch when no tracer is installed: the jitted call keeps
# one call site either way (see ``_call_program``)
_NO_ANNOTATION = contextlib.nullcontext()


def _timed(fn, split: list, i: int):
    """``fn`` (of two arguments, as each of ``_row_calls`` is), with the
    nanoseconds spent inside it added to ``split[i]``."""
    now = time.perf_counter_ns

    def run(a, b):
        t0 = now()
        out = fn(a, b)
        split[i] += now() - t0
        return out
    return run


def _host_nbytes(args) -> int:
    """Bytes of the host arrays among a launch's arguments (a dict's
    values counted each): what the jitted call has to move to the
    device.  From ``nbytes``: nothing is copied to count it."""
    n = 0
    for a in args:
        if isinstance(a, np.ndarray):
            n += a.nbytes
        elif isinstance(a, dict):
            for v in a.values():
                n += v.nbytes
    return n


def _pack_results(sampled, fin, counts=None):
    """Everything the HOST reads of a launch, in one int32 vector: the
    sampled tokens, the finiteness flags as 0/1 and, of a model with
    expert layers, what they counted.  One array is one device-to-host
    transfer, which the launch starts itself (``copy_to_host_async``)
    the moment it is dispatched.  Traced inside a step program."""
    parts = [sampled.reshape(-1), fin.reshape(-1).astype(jnp.int32)]
    if counts is not None:
        parts.append(counts)
    return jnp.concatenate(parts)


def _sampled_tail(logits, samp, packed):
    """``LLMEngine._get_tail_prog``'s program: (sampled, packed)."""
    with jax.named_scope("sample"):
        sampled = sample_tokens(logits, samp)
        return sampled, lax.dynamic_update_slice(packed, sampled, (0,))


def _unpack_results(host, shape):
    """``_pack_results``' vector, on the host, as (sampled, finiteness
    flags, expert counts): the first two of ``shape`` ([Lq] of a step,
    [K, B] of a decode window), the counts whatever lies behind them
    (empty where nothing was counted)."""
    n = math.prod(shape)
    return (host[:n].reshape(shape), host[n:2 * n].reshape(shape) != 0,
            host[2 * n:])


class _AheadAbandoned(Exception):
    """A dispatch ahead of the in-flight launch's commit met something it
    may not do (``reason``: a key of ``ahead_fallbacks``): this call
    commits that launch first and dispatches as the synchronous engine
    does.  What the attempt did before is kept: admissions, page
    reservations and page copies are what that dispatch would do too."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class LLMEngine:
    """Continuous-batching serving loop over one LlamaForCausalLM.

    Parameters
    ----------
    model: LlamaForCausalLM (weights are snapshot via decode_params()).
    max_num_seqs: decode-batch capacity (the padded decode batch size).
    block_size: KV page size in tokens (must satisfy the paged kernel's
        bs % 8 == 0 to be kernel-eligible on TPU).
    num_blocks: page-pool size.  Default sizes the pool so every batch
        slot can reach max_model_len (no preemption under the default).
    max_model_len: longest prompt+generation the engine accepts; fixes
        the static block-table width of the decode program.
    max_prefill_tokens: per-STEP prompt-token budget.  Prompts longer
        than this are prefilled in chunks across steps (decode of the
        running set proceeds every step regardless).
    prefill_token_bucket: the ragged step's flat token buffer is padded
        to max_num_seqs for decode-sized launches and to a multiple of
        this above it, bounding the number of compiled step programs by
        max_prefill_tokens / bucket + 1.
    enable_prefix_caching: content-hash full KV pages and reuse them
        across requests sharing a token prefix (BlockManager docstring
        has the page lifecycle).  Greedy output is byte-identical on
        or off.
    drafter: a spec_decode.Drafter (or the string "ngram" for the
        prompt-lookup drafter) proposing draft tokens; None disables
        speculative decoding engine-wide.
    spec_k: default per-request draft length (requests may override via
        add_request(spec_k=); 0 means plain decode).
    max_spec_k: hard per-round draft ceiling; fixes the ragged program's
        static logit-row width max_num_seqs * (max_spec_k + 1).
    spec_accept_floor / spec_window: once a request has sent spec_window
        drafts to verify, speculation auto-disables for it if its
        lifetime acceptance rate sits below the floor (the drafter is
        not helping; stop paying the verify overhead).
    kv_dtype: "float32" (full-width pages in the model dtype) or "int8"
        (pages quantize symmetrically at commit time with per-page-per-
        head f32 scales in a parallel pool; attention dequantizes inline
        at read time).  Int8 pages cost ~4x less HBM per resident
        sequence; greedy outputs are near-identical, gated by the
        tolerance oracle in tests rather than byte-equality.
    retain_outputs: keep every finished RequestOutput in the dict that
        ``run()`` returns.  A long-running server (the HTTP frontend)
        passes False — outputs are delivered through each request's
        ``on_finish`` callback instead, so finished requests cost no
        memory once their stream closes.
    tp: tensor-parallel degree.  tp > 1 lays the SAME ragged step over a
        1-D device mesh via shard_map: attention heads (Hq and Hkv) and
        the KV/scale page pools shard per chip along the head axis,
        block tables and (cu_seqlens, kv_lens) replicate, and one
        all-gather of per-shard attention heads (plus logit slices when
        vocab_size % tp == 0) runs INSIDE the compiled step — the host
        still sees one launch per step and ``compile_counts`` still
        counts one attention program kind.  Requires num_attention_heads
        % tp == 0 and num_key_value_heads % tp == 0.  Head partitioning
        is by contiguous blocks, so GQA group structure is preserved and
        greedy outputs stay byte-identical to tp=1.  Host bookkeeping
        (BlockManager, scheduler, sampling params) is untouched — it is
        mesh-blind.  Testable on CPU via
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    overlap: run the step loop as a dispatch/completion PIPELINE (the
        default).  A ``step()`` call that finds launch n in flight first
        schedules, packs and launches n+1 from the state launch n WILL
        leave (dispatch AHEAD: every row's position is known before n
        returns, and the one thing that is not, the id of the token n
        samples, passes from n's ``sampled`` to n+1's input on the
        device), and only then blocks on n and commits it: the host's
        whole turn runs under a step of the device, not after it.
        Inside a call there are two tickets, between calls at most one
        (so ``abort``, ``has_unfinished`` and recovery see what they
        did).  Commit order is dispatch order.  Where launch n+1 needs
        something only commit n can give, the call commits first and
        dispatches after, as the synchronous step does: a decode window
        on either side, a drafter, a ``kv_tier``, a pressure controller,
        an armed fault plan, a row with a repetition penalty, or a
        reservation or page copy only a preemption could meet
        (``summary()["ahead_fallbacks"]`` counts each by name beside
        ``launches_ahead``).  A request that commit n retires on a stop
        token may already ride n+1: that row is dropped unapplied
        (``ahead_rows_dropped``) and its pages are given back when n+1
        has completed.  Greedy output is byte-identical on or off and a
        token bucket still has ONE program; the visible difference is
        that a request's outputs surface one ``step()`` call later and
        ``run()`` takes one extra draining call.  False restores the
        fully synchronous launch-then-block step.
    decode_window: K > 1 runs STEADY pure-decode packs as one
        device-resident K-step window: a single compiled program loops
        attention -> logit-processor chain -> sampling -> paged K/V
        append K times on device (sampled tokens, per-row PRNG keys,
        ``seen`` masks, and kv_lens carried as loop state), and the host
        drains up to K committed tokens per launch instead of paying a
        round-trip per token.  Rows hitting eos/length freeze under an
        active-mask (the loop exits early when every row is done); block
        tables refresh only at window boundaries, with K tokens of page
        slack pre-reserved per row before launch — when the pool cannot
        cover the window the step falls back to the per-step path (never
        preempting for a window).  Mixed packs (prefill chunks, verify
        rows) and waiting-queue pressure always take the per-step path,
        so admission latency is unchanged.  Greedy output is
        byte-identical to decode_window=1; ``compile_counts`` gains at
        most one "scan" program kind, only when a window launches.

    devices: the ``tp`` jax devices this engine's weights and page
        pools live on (default: the first ``tp`` of ``jax.devices()``).
        A replica router passes each replica its own, so that replicas
        do not share one chip.

    The step programs are two drivers round ONE forward
    (``layer_stack.forward``: embed, the layers by kind, norm, head):
    ``_make_ragged_fn`` (segments, forward, sample) and, for
    ``decode_window`` > 1, ``_make_window_fn`` (a ``lax.while_loop``
    round the same forward at Tq = B).  ``kv_dtype`` adds pools and an
    input (the scale pools, the fresh-page mask), not a builder: the
    ``gqa`` layer kind quantizes at commit when the pages it is handed
    are int8.

    Which attention and which matmul implementation the step programs
    run is decided once here, from the devices' platform and the
    kernels' static shape claims, and is readable as
    ``attention_path`` / ``matmul_path`` (and in ``summary()["paths"]``
    with every program built so far).  On a TPU the Pallas kernels run
    wherever they claim the shape; a kernel Mosaic then refuses fails
    the program's compile, it does not fall back.  The XLA reference
    paths run on the CPU platform and for shapes a kernel does not
    claim, and the path string says why.

    The engine is SINGLE-THREADED by design: add_request/step/abort must
    all be called from one thread (the frontend's EngineRunner owns that
    thread and bridges other threads in via queues drained at step
    boundaries).  abort() in particular relies on being between steps,
    when pool state is consistent.
    """

    def __init__(self, model, *, max_num_seqs: int = 8, block_size: int = 16,
                 num_blocks: int | None = None, max_model_len: int | None = None,
                 max_prefill_tokens: int = 512,
                 prefill_token_bucket: int = 64,
                 enable_prefix_caching: bool = True,
                 drafter=None, spec_k: int = 0, max_spec_k: int = 8,
                 spec_accept_floor: float = 0.35, spec_window: int = 32,
                 retain_outputs: bool = True,
                 fault_plan=None, pressure=None,
                 kv_dtype: str = "float32", tp: int = 1,
                 tracer=None, overlap: bool = True,
                 decode_window: int = 1,
                 weight_dtype: str = "float32",
                 kv_tier=None, devices=None):
        cfg = model.config
        self.config = cfg
        # which layers the model has: (attention kind, FFN kind) each.
        # ``gqa`` + ``swiglu`` throughout is the dense decoder; a model
        # of other kinds says so itself (``config.layer_kinds()``)
        self._layer_kinds = cfg.layer_kinds() \
            if hasattr(cfg, "layer_kinds") \
            else [("gqa", "swiglu")] * cfg.num_hidden_layers
        self._latent = any(a in _ls.LATENT_KINDS
                           for a, _ in self._layer_kinds)
        # window layers keep pools and a block table of their own
        self._windowed = any(a in _ls.WINDOW_KINDS
                             for a, _ in self._layer_kinds)
        # the dense decoder alone runs as one scan over stacked weights
        self._scanned = all(k == ("gqa", "swiglu")
                            for k in self._layer_kinds)
        self._has_experts = any(f in ("moe", "moe_reglu")
                                for _, f in self._layer_kinds)
        # state-space layers keep a state a sequence, by batch slot
        self._stateful = any(a in _ls.STATE_KINDS
                             for a, _ in self._layer_kinds)
        if self._stateful:
            if not any(a in _ls.WINDOW_KINDS for a, _ in self._layer_kinds):
                raise ValueError(
                    "state-space layers without sliding-window layers: "
                    "the state rides beside the TWO page tables of a "
                    "window model, and no step program has it beside one")
            _refuse_state_options(
                enable_prefix_caching=bool(enable_prefix_caching),
                drafter=drafter, decode_window=decode_window,
                kv_tier=kv_tier, kv_dtype=kv_dtype,
                weight_dtype=weight_dtype, tp=tp)
        if self._latent:
            _refuse_latent_options(
                kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp,
                drafter=drafter, decode_window=decode_window,
                kv_tier=kv_tier)
        if self._windowed:
            _refuse_window_options(
                kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp,
                drafter=drafter, decode_window=decode_window,
                kv_tier=kv_tier,
                enable_prefix_caching=bool(enable_prefix_caching))
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'float32' or 'int8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        if weight_dtype not in ("float32", "int8", "int4"):
            raise ValueError(
                "weight_dtype must be 'float32', 'int8' or 'int4', "
                f"got {weight_dtype!r}")
        self.weight_dtype = weight_dtype
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if self.tp > 1:
            if (cfg.num_attention_heads % self.tp
                    or cfg.num_key_value_heads % self.tp):
                raise ValueError(
                    f"tp={self.tp} must divide num_attention_heads="
                    f"{cfg.num_attention_heads} and num_key_value_heads="
                    f"{cfg.num_key_value_heads} (contiguous head "
                    "partition keeps GQA groups on one shard)")
        devices = list(jax.devices()[:self.tp] if devices is None
                       else devices)
        if len(devices) != self.tp:
            raise ValueError(
                f"tp={self.tp} needs {self.tp} devices, got {len(devices)} "
                "(for CPU testing set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.tp})")
        self.devices = devices
        self._platform = devices[0].platform
        self._mesh = Mesh(np.asarray(devices), ("tp",)) \
            if self.tp > 1 else None
        # the layer-stacked (and quantized) copy is built where it will
        # live: on the process's default device a replica's transient
        # copy would sit on chip 0 beside the model and replica 0's own
        with jax.default_device(devices[0]):
            # the dense decoder's export is a layer-stacked COPY (what
            # its scan runs over); a model of differing layers exports
            # its own arrays layer by layer and the engine holds them
            # once
            self.params = model.decode_params()
            # the step's activations keep the model's float dtype even
            # when the embed table becomes a quantized pool + scales
            self._act_dtype = self.params["embed"].dtype
            # {name: its heads} of the leaves this engine holds
            # [L, heads, d, in], which the float ``mm`` contracts on
            # the weight's LAST axis (``_weight_ops``): q, k and v of
            # the stacked copy, which is the engine's own to lay out; a
            # quantized pool keeps the kernel's [in, out], and arrays
            # held once are the model's
            self._out_major = {}
            if self.weight_dtype != "float32":
                self.params = self._quantize_params(self.params)
            elif self._scanned:
                self._out_major = {"wq": cfg.num_attention_heads,
                                   "wk": cfg.num_key_value_heads,
                                   "wv": cfg.num_key_value_heads}
                self._hold_out_major(self.params["layers"])
        # the unembedding shards over vocab only when it divides evenly
        # (padding the vocab axis would poison the per-row finiteness
        # flag); otherwise the head matmul replicates and the per-layer
        # attention-head all-gather is the step's collective
        self._shard_head = self.tp > 1 and cfg.vocab_size % self.tp == 0
        self.max_num_seqs = int(max_num_seqs)
        self.block_size = int(block_size)
        self.max_model_len = int(max_model_len or cfg.max_position_embeddings)
        self.max_prefill_tokens = int(max_prefill_tokens)
        self.prefill_token_bucket = int(prefill_token_bucket)
        self.enable_prefix_caching = bool(enable_prefix_caching)

        # static block-table width: pages needed by a max-length sequence
        self.nblk = -(-self.max_model_len // self.block_size)
        if num_blocks is None:
            num_blocks = 1 + self.max_num_seqs * self.nblk
        # the window layers' pool is sized HERE, from the model's window
        # and this engine's own limits: the most max_num_seqs running
        # sequences can hold at once, each a window of pages, the pages
        # of the longest chunk in flight and one for a window that
        # starts inside a page.  So ``num_blocks`` governs the global
        # layers alone, and a window layer never runs out
        self._window = int(cfg.sliding_window_size) if self._windowed else 0
        self._window_blocks = 0
        if self._windowed:
            per_seq = min(self.nblk,
                          -(-self._window // self.block_size)
                          + -(-self.max_prefill_tokens // self.block_size)
                          + 1)
            self._window_blocks = 1 + self.max_num_seqs * per_seq
        self.blocks = BlockManager(
            num_blocks, self.block_size,
            enable_prefix_caching=self.enable_prefix_caching,
            window=self._window, window_blocks=self._window_blocks)
        if self.blocks.num_free < self.nblk:
            raise ValueError(
                f"num_blocks={num_blocks} cannot hold even one "
                f"max_model_len={self.max_model_len} sequence "
                f"({self.nblk} pages needed)")
        # hierarchical KV: a HostSpillPool (inference/kv_tier.py) turns
        # EVICT_PARKED from kill into spill — pages quarantine in the
        # pool and move host-side at the step-boundary drain, and
        # admission gets them back as ordinary prefix-cache content
        self.kv_tier = kv_tier
        if kv_tier is not None:
            self.blocks.spill_on_evict = True
        # chain hashes restored from the tier and not yet claimed by an
        # admission hit (prefetch-hit attribution is by hash, so block
        # reuse can never misattribute)
        self._staged_hashes: set = set()

        self._nh = cfg.num_attention_heads
        self._attn = self._attention_by_kind()
        L = cfg.num_hidden_layers
        dt = self._act_dtype
        # a page's (heads, row width), by POOL: the block table's pool
        # and, with window layers, theirs (a latent kind caches one row
        # a token, one "head" of the row's stored width, and the kinds
        # of one model need not agree on it)
        if self._latent:
            glob, wind = self._latent_pool_kinds()
            self._kvh, self._hd = 1, _mla.page_width(glob.dc + glob.dr)
            self._kvh_w, self._hd_w = (1, _mla.page_width(
                wind.dc + wind.dr)) if wind is not None else (0, 0)
        else:
            self._kvh = cfg.num_key_value_heads
            # the configuration's own where it states one: the heads'
            # total need not be the hidden size (28 x 128 over 2560)
            self._hd = int(getattr(cfg, "head_dim", 0)
                           or cfg.hidden_size // self._nh)
            if hasattr(cfg, "page_shape"):
                # a cached row need not be laid out as the heads that
                # made it (two heads of 64 side by side: phi4flash)
                self._kvh, self._hd = cfg.page_shape()
            self._kvh_w, self._hd_w = self._kvh, self._hd
        self._kw = self._vw = self._ki = self._sc = self._ss = None
        # a layer's index into the pools of its kind (None: its place
        # in the model, one pool for all layers)
        self._pool_index = None
        with jax.default_device(devices[0]):
            is_w = [a in _ls.WINDOW_KINDS for a, _ in self._layer_kinds]
            # layers whose rows live under the block table
            n_g = sum(not w and a not in _ls.POOLLESS_KINDS
                      for w, (a, _) in zip(is_w, self._layer_kinds))
            if self._windowed:
                # the global layers' pages live as long as their
                # sequence ([Lg, num_blocks, ...], the block table's),
                # the window layers' come and go ([Lw, Nw, ...], the
                # window table's): a layer's index is its place among
                # the layers that share its pools
                self._pool_index = [sum(is_w[:i]) if w
                                    else i - sum(is_w[:i])
                                    for i, w in enumerate(is_w)]
            if self._latent:
                # ONE pool a kind of layer, a row [c | k_rope | 0...] a
                # token: written in place and read where it lies; the
                # window layers' pool has a row width of its own
                self._kc = jnp.zeros((L - sum(is_w), num_blocks,
                                      self.block_size, self._hd), dt)
                if self._windowed:
                    self._kw = jnp.zeros(
                        (sum(is_w), self._window_blocks, self.block_size,
                         self._hd_w), dt)
                if glob.index is not None:
                    # the indexer's keys, a row a token a full layer,
                    # under the block table and its page ids: no
                    # allocator state of their own
                    self._ki = jnp.zeros((L - sum(is_w), num_blocks,
                                          self.block_size, glob.index.d), dt)
                self._vc = self._ks = self._vs = None
            elif self._windowed:
                # TWO pairs of pools
                page = (self._kvh, self.block_size, self._hd)
                self._kc = jnp.zeros((n_g, num_blocks) + page, dt)
                self._vc = jnp.zeros_like(self._kc)
                self._kw = jnp.zeros((sum(is_w), self._window_blocks)
                                     + page, dt)
                self._vw = jnp.zeros_like(self._kw)
                self._ks = self._vs = None
                if self._stateful:
                    # a slot a running sequence and one nobody holds
                    # (what a launch's rows of no tokens name): the
                    # convolution's tails in the served type, the scan's
                    # state float32
                    conv, scan = cfg.state_shapes(self.max_num_seqs + 1)
                    self._sc = jnp.zeros(conv, dt)
                    self._ss = jnp.zeros(scan, jnp.float32)
            elif self.kv_dtype == "int8":
                # int8 pages + a parallel per-page-per-head f32 scale
                # pool (symmetric: float = int8 * scale).  Scales are
                # written at commit time inside the step program; the
                # kernel/reference dequantizes inline at read time, so
                # every host-side page structure (hashing, CoW, sharing,
                # parking) is unchanged.
                self._kc = jnp.zeros((L, num_blocks, self._kvh,
                                      self.block_size, self._hd), jnp.int8)
                self._vc = jnp.zeros_like(self._kc)
                self._ks = jnp.zeros((L, num_blocks, self._kvh),
                                     jnp.float32)
                self._vs = jnp.zeros_like(self._ks)
            else:
                # "float32" means full-width model dtype (f32/bf16) pages
                self._kc = jnp.zeros((L, num_blocks, self._kvh,
                                      self.block_size, self._hd), dt)
                self._vc = jnp.zeros_like(self._kc)
                self._ks = self._vs = None
        if self.tp == 1:
            # the unstacked leaves (embedding, head, final norm) are the
            # model's own arrays: a replica off the model's device gets
            # its copy of them here, and nothing moves on the device
            # that already holds them
            self.params = jax.device_put(self.params, devices[0])
            # the pools are COMMITTED to the device like the parameters
            # (the same buffers, no copy): a program's outputs are, so
            # the first program launched would otherwise be lowered a
            # second time at its next launch, whenever that comes
            self._set_pools(jax.device_put(self._pools(), devices[0]))
        else:
            # lay the pools and the head-partitioned weights out on the
            # mesh ONCE at construction; every step launch then runs
            # without resharding transfers
            self.params = self._shard_params(self.params)
            kv_sh = NamedSharding(self._mesh, P(None, None, "tp"))
            self._kv_sharding = kv_sh
            self._kc = jax.device_put(self._kc, kv_sh)
            self._vc = jax.device_put(self._vc, kv_sh)
            if self._ks is not None:
                self._ks = jax.device_put(self._ks, kv_sh)
                self._vs = jax.device_put(self._vs, kv_sh)
        self._slots = self._pool_slots() if self._latent else None
        # an indexer's selection (0: the model has none): what a launch's
        # index_keys_selected counts against
        self._index_topk = max(
            (a.index.topk for a in self._attn.values()
             if getattr(a, "index", None) is not None), default=0)
        # scale-reset feed: pages BlockManager handed out since the last
        # launch (their old scales are dead); consumed by _launch_ragged
        self._fresh_np = np.zeros((num_blocks,), bool)

        self._waiting: deque = deque()
        self._running: list = []
        self._finished: dict = {}
        self._next_rid = 0
        self._arrival = 0
        self.retain_outputs = bool(retain_outputs)

        # stable batch slots (pure-decode steps pack rows in slot order,
        # so a steady batch keeps a stable layout) + persistent host-side
        # buffers for the decode fast path: rows are updated
        # incrementally (grow/retire/CoW bump the table version, any
        # membership/order change breaks the layout signature) instead of
        # rebuilt from scratch every token
        B = self.max_num_seqs
        self._slot_used = [False] * B

        # speculative decoding: a host-side drafter proposes up to
        # max_spec_k tokens per decode-ready sequence; each speculative
        # sequence rides the step's single ragged launch as one
        # [last_token, drafts...] row
        if drafter == "ngram":
            from .spec_decode import NGramDrafter
            drafter = NGramDrafter()
        self.drafter = drafter
        self.spec_k = int(spec_k)
        self.max_spec_k = int(max_spec_k)
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_window = int(spec_window)
        # logit-row width of the ragged program: spec rows need k+1
        # scored positions each; without a drafter one row == one logit.
        # The program returns raw per-position logits (for host-side
        # draft acceptance) only when a drafter exists.
        self._with_logits = drafter is not None
        self._Lq = B * (self.max_spec_k + 1) if self._with_logits else B
        # Where the MODEL says so (a vocabulary at which the sampled
        # rows' sorts are most of a step program's code), a step
        # program ends at the greedy token and hands its logits on; a
        # launch that holds a sampled row is followed by ONE program of
        # its own, the same for every token bucket (``_get_tail_prog``)
        self._tail_apart = bool(getattr(cfg, "sampled_tail_apart", False))
        self._tail_prog = None
        self.attention_path = self._resolve_attention_path()
        self.matmul_path = self._resolve_matmul_path()
        # program name ("ragged:64", "window:4") -> the paths it was built
        # with; filled at each program BUILD, read by summary()
        self.program_paths: dict = {}

        # decode fast-path buffers (general mixed launches repack from
        # scratch; steady pure-decode steps reuse these).  Two sets:
        # with overlap on, launches alternate buffers so the host never
        # rewrites arrays a still-in-flight launch may be aliasing
        # (overlap off only ever touches buffer 0).  lidx is read-only
        # to the program and safely shared between them.
        self.overlap = bool(overlap)
        # the block table a launch is handed: a row a sequence and the
        # null row; with window layers two of them, stacked (the global
        # layers' first)
        self._bt_shape = ((2,) if self._windowed else ()) \
            + (B + 1, self.nblk)
        self._dbufs = (
            _DecodeBufs(B, self._bt_shape, self._Lq, cfg.vocab_size),
            _DecodeBufs(B, self._bt_shape, self._Lq, cfg.vocab_size))
        self._d_cur = 0                   # buffer of the latest launch
        self._d_lidx = np.minimum(np.arange(self._Lq), B - 1) \
            .astype(np.int32)
        # dispatch/completion pipeline state: the one ticket in flight
        # between step() calls, and inside a call the one dispatched
        # ahead of its commit (queued behind it on the device)
        self._inflight: _StepTicket | None = None
        self._queued: _StepTicket | None = None
        self._pending_finished: list = [] # finishes from an abort() flush
        # while a dispatch runs: the ticket it is dispatched ahead of
        # (None: nothing in flight, every row's state is committed), and
        # otherwise why this launch is not ahead (``ahead_fallbacks``)
        self._ahead_of: _StepTicket | None = None
        self._not_ahead = "idle" if self.overlap else "sync"
        # launches dispatched before the launch in front of them was
        # committed; the others by reason; rows such a launch held of a
        # request the commit in front of it retired (dropped unapplied)
        self.launches_ahead = 0
        self.ahead_fallbacks: dict = {}
        self.ahead_rows_dropped = 0
        # times a preemption made the scheduler re-filter rows it had
        # taken for the launch it was preparing
        self.sched_refilters = 0
        # what a launch takes as ``prev`` when nothing is in flight:
        # placed as a launch's ``sampled`` comes back, so that a bucket
        # has ONE program whichever it is handed
        self._no_prev = jax.device_put(
            np.zeros((self._Lq,), np.int32), devices[0] if self.tp == 1
            else NamedSharding(self._mesh, P()))

        # program cache: ONE attention program kind, keyed only by the
        # flat-token bucket Tq.  The counter dict is the test-visible
        # compile-count regression guard: every program BUILD (not call)
        # bumps its kind, so a mixed stream can assert "exactly N
        # programs" without reaching into the caches.
        self._ragged_progs: dict = {}
        self._cow_prog = None
        self.compile_counts = {"ragged": 0, "cow": 0}
        # device-resident decode window (K > 1): one extra program kind
        # ("scan") cached here, NOT in _ragged_progs — the decode/prefill
        # program-count properties stay exact.  The "scan" key joins
        # compile_counts only when a window actually compiles, so
        # decode_window=1 engines keep the historical exact-dict budgets.
        self.decode_window = int(decode_window)
        if self.decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}")
        self._window_prog = None
        # padding accounting: real packed tokens vs bucket width, plus
        # what the pre-ragged four-program engine would have padded to
        # (serve_bench --mixed reports the two ratios side by side)
        self.pad_stats = {"real": 0, "padded": 0, "legacy_padded": 0,
                          "kv_pages": 0, "kv_pages_window": 0,
                          "kv_write_pages": 0, "kv_write_tokens": 0,
                          "index_keys_visible": 0, "index_keys_selected": 0,
                          "state_starts": 0, "state_rows": 0}
        # passes of the sampling epilogue, and those whose launch held a
        # sampled row (temps > 0): the device runs the sampled chain in
        # exactly those (sampling.sample_tokens branches on the same)
        self.sample_stats = {"launches": 0, "chain_launches": 0}
        # launches dispatched so far: the STEP ID every trace event
        # carries (the dispatch half of a step the id of the launch it
        # prepares, launches + 1; the completion half its ticket's)
        self.launches = 0
        # completions that found the launch's execution already ended
        # when they came to read it (_complete)
        self.reads_ready = 0
        self._evictions_seen = 0
        self.peak_resident_seqs = 0
        # what the step's expert layers counted (in the order the step
        # program returns them), summed at completion
        self.moe_counts = {"moe_pairs_here": 0, "moe_pairs_all": 0,
                           "moe_experts_touched": 0, "moe_load_max": 0}
        self._experts_held = sum(
            cfg.experts_held for _, f in self._layer_kinds
            if f in ("moe", "moe_reglu"))
        self._launch_pages: dict = {}     # the latest launch's page counts
        self._launch_call: dict = {}      # and, traced, its call_ns, arg_bytes
        self.stats = ServingStats()
        self.stats.set_decode_window(self.decode_window)
        self.stats.set_weight_residency(
            self.weight_dtype, self.weight_bytes_resident(),
            self.weight_bytes_resident_per_shard())
        # per-request flight recorder (inference/flight.py): None means
        # every request-lifecycle seam is one attribute check and
        # nothing else — the tracer's zero-cost contract
        self.flight = None
        # step-timeline tracer (profiler/trace.py): None means every
        # instrumentation seam is one attribute check and nothing else —
        # the same zero-cost contract the fault plan keeps
        self.tracer = None
        self._blocked_ns = 0      # inside _complete's block, this step()
        self._split = None        # the commit under way, by what a row calls
        self._emits = None        # ... and by sink, what it has emitted
        self._trace_track = "engine"
        self._commit_step = 0         # the ticket's id while it commits
        self._cow_n = 0               # CoW launches and their host time
        self._cow_ns = 0              # since engine.schedule began
        self._sched_open = None       # (start, args) of that span, until
        #                               the launch it chose is packed
        # resolve this engine's launch geometry from the tuning cache
        # once at build — pure host-side dict reads (no compile) whose
        # provenance summary() and serve_bench records surface
        self._tuning_report = self._resolve_tuning()

        # fault-tolerance surfaces: a FaultPlan drives deterministic
        # chaos through the step/pool seams (None -> one attribute check
        # per step); a DegradationController (inference/pressure.py)
        # sheds load in tiers before preemption becomes necessary
        self.fault_plan = None
        self.set_fault_plan(fault_plan)
        self.pressure = pressure
        self.set_tracer(tracer)

    def set_fault_plan(self, plan) -> None:
        """Install (or clear) a FaultPlan on this engine and its pool.
        The runner re-installs the same plan on a rebuilt engine, so a
        schedule survives recovery with its consumed faults consumed."""
        self.fault_plan = plan
        self.blocks._fault_hook = plan.pool_exhausted \
            if plan is not None else None
        if plan is not None:
            plan.tracer = self.tracer
            plan.trace_track = self._trace_track

    def set_tracer(self, tracer) -> None:
        """Install (or clear) a step-timeline Tracer on this engine (and
        on its fault plan, so injected faults land in the trace).  With
        None installed the step loop performs no trace work at all.  A
        tracer also watches the collector (``host.gc`` spans) for as
        long as some engine has it installed."""
        if self.tracer is not None:
            self.tracer.unwatch_gc(self)
        self.tracer = tracer
        if tracer is not None:
            self._trace_track = tracer.register("engine")
            tracer.watch_gc(self)
        if self.fault_plan is not None:
            self.fault_plan.tracer = tracer
            self.fault_plan.trace_track = self._trace_track

    def set_flight(self, recorder) -> None:
        """Install (or clear) a per-request FlightRecorder
        (inference/flight.py).  With None installed the request
        lifecycle seams perform no forensic work at all."""
        self.flight = recorder

    def _tier(self) -> int:
        """Current degradation tier (0 when no pressure controller)."""
        return 0 if self.pressure is None else self.pressure.state

    def dump_trace(self, path) -> int:
        """Write this engine's step timeline as Chrome trace-event JSON
        (Perfetto-loadable); returns the number of events written.
        Raises when tracing was never enabled."""
        if self.tracer is None:
            raise RuntimeError(
                "tracing is not enabled: build the engine with tracer= "
                "or call set_tracer() first")
        return self.tracer.dump(path)

    # ------------------------------------------------------------------
    # quantized weight pools (weight_dtype != "float32")
    # ------------------------------------------------------------------

    def _quantize_params(self, params) -> dict:
        """Quantize decode_params ONCE at engine build into the pool
        layout the fused dequant-matmul kernel streams.

        Every projection/MLP weight ``name`` becomes a ``name_q``
        quantized pool + ``name_s`` f32 scale tensor (int8:
        per-output-channel; int4: nibble-packed with per-128-row-group
        scales — see ops/pallas/quant_matmul.py); the embedding becomes
        a per-vocab-row pool dequantized inline at gather.  Norms stay
        f32 — they are O(H) gauge vectors, not bandwidth.  Runs BEFORE
        ``_shard_params``: column-slicing commutes with quantization,
        so tp=N shards the pools and scales by the same head/column
        blocks with no resharding."""
        wdt = self.weight_dtype
        layers = params["layers"]
        out_layers = {"ln1": layers["ln1"], "ln2": layers["ln2"]}
        quant = jax.vmap(lambda w: _qm.quantize_weight(w, wdt))
        for name in ("wq", "wk", "wv", "wo", "gate", "up", "down"):
            q, s = quant(layers[name])
            out_layers[name + "_q"] = q
            out_layers[name + "_s"] = s
        eq, es = _qm.quantize_embedding(params["embed"], wdt)
        hq, hs = _qm.quantize_weight(params["head"], wdt)
        return {"layers": out_layers, "embed_q": eq, "embed_s": es,
                "norm_f": params["norm_f"], "head_q": hq, "head_s": hs}

    def _hold_out_major(self, layers) -> None:
        """Turn the ``_out_major`` leaves of the stacked copy from the
        export's [L, in, heads * d] to [L, heads, d, in], in place in
        ``layers``.

        XLA lays q, k and v out head-major for the attention kernel and
        pushes that back through the rotary into the products, so it
        reads these weights as [heads, d, hidden]: from [in, out] that
        was a transposing copy of each matrix in every layer of every
        step (7 to 9% of a dense step on the v5e); handed [heads, d,
        in] it slices the layer inside the product that reads it, as it
        does for the other four matrices.  Leaf by leaf, each export
        leaf dropped as its replacement stands: the transient is one
        stack, not three."""
        for name, heads in self._out_major.items():
            w = layers.pop(name)
            n, width, out = w.shape
            # (one primitive, one result: transposed, then split by head)
            layers[name] = jax.block_until_ready(lax.reshape(
                w, (n, heads, out // heads, width), dimensions=(0, 2, 1)))

    def _latent_pool_kinds(self):
        """(sizes of the latent kind under the block table, sizes of the
        window layers' latent kind or None): a pool holds rows of one
        width, so a model has at most one latent kind a pool."""
        kinds = {a for a, _ in self._layer_kinds}
        if not kinds <= set(_ls.LATENT_KINDS):
            raise ValueError(
                f"layers of kinds {sorted(kinds)}: latent and grouped-"
                "query layers in one model need K/V pools beside the "
                "latent pool, which no step program has")
        glob = [k for k in kinds if k not in _ls.WINDOW_KINDS]
        wind = [k for k in kinds if k in _ls.WINDOW_KINDS]
        if len(glob) != 1 or len(wind) > 1:
            raise ValueError(
                f"latent kinds {sorted(kinds)}: one kind under the block "
                "table and at most one under the window table")
        return (self._attn[glob[0]],
                self._attn[wind[0]] if wind else None)

    def _pool_slots(self) -> dict:
        """{latent kind: the places of its pools among ``_pools()``}: a
        kind under the block table has the latent pool and, with an
        indexer, the index keys' after it; a window kind the window
        table's."""
        held = [n for n in self._POOLS if getattr(self, n) is not None]
        return {k: tuple(held.index(n) for n in (
            ("_kw",) if k in _ls.WINDOW_KINDS else
            ("_kc", "_ki") if self._attn[k].index is not None
            else ("_kc",)))
            for k in {a for a, _ in self._layer_kinds}}

    def _attention_by_kind(self) -> dict:
        """{grouped-query attention kind: its query heads ``nh`` (a
        shard's) and its rotary ``rope(x, pos)``}, what a step program
        hands each kind: the configuration's own where its kinds differ
        in them (``attention_by_kind``: ``models/laguna.py``), else one
        head count and one theta for every kind, and no rotary on a kind
        without positions."""
        cfg = self.config
        if hasattr(cfg, "attention_by_kind"):
            return cfg.attention_by_kind()
        rope = functools.partial(_rope_positions, theta=cfg.rope_theta)
        return {a: SimpleNamespace(nh=self._nh // self.tp,
                                   rope=None if a == "gqa_nope" else rope)
                for a, _ in self._layer_kinds}

    def _resolve_attention_path(self) -> str:
        """Which attention the step programs run, decided once from the
        platform and the kernel's static claim.  The interpreted kernel
        runs its row, block and page loops as XLA loops on the host
        EVERY launch, so off the TPU the XLA reference (term-identical
        math) serves unless a test forces the interpreter."""
        if _pa.INTERPRET is True:
            return "pallas-interpret"
        if self._platform != "tpu":
            return f"xla-reference ({self._platform} platform)"
        if self._latent:
            # every latent kind's heads, row and latent, and an
            # indexer's key, have to be ones the kernels claim
            whys = [_mla.ineligible(
                a.nh, _mla.page_width(a.dc + a.dr), a.dc, self.block_size,
                self._act_dtype, launch=(self.max_num_seqs + 1, self.nblk,
                                         self.blocks.num_blocks),
                index_dim=None if a.index is None else a.index.d)
                for a in self._attn.values()]
            why = next((w for w in whys if w is not None), None)
            return "pallas" if why is None else f"xla-reference ({why})"
        # every kind's head count has to be one the kernel claims
        whys = [_pa.ineligible(a.nh, self._kvh // self.tp,
                               self._hd, self.block_size,
                               jnp.int8 if self.kv_dtype == "int8"
                               else self._act_dtype,
                               launch=(self.max_num_seqs + 1, self.nblk,
                                       self.blocks.num_blocks))
                for k, a in self._attn.items() if k in _ls.ATTENTION]
        # and a state-space kind's sizes ones the scan kernel claims
        whys += [_scan.ineligible(a.di, a.n)
                 for k, a in self._attn.items() if k in _ls.STATE_KINDS]
        why = next((w for w in whys if w is not None), None)
        return "pallas" if why is None else f"xla-reference ({why})"

    def _resolve_matmul_path(self) -> str:
        """Which matmul the step programs run: XLA's own dot over float
        weights; over quantized pools the fused dequant kernel on the
        TPU (or under a forced interpreter) wherever it claims the
        weight's shape, else its XLA fake-quant reference."""
        wdt = self.weight_dtype
        # weight name -> why the kernel does not take it; the step
        # bodies route by this same record (``_weight_ops``)
        self._qmm_refused: dict = {}
        if wdt == "float32":
            return "xla-dense"
        if _qm.INTERPRET is True:
            kernel = "pallas-quant-interpret"
        elif self._platform == "tpu":
            kernel = "pallas-quant"
        else:
            return f"xla-fake-quant ({self._platform} platform)"
        for name, q in [(n[:-2], q) for n, q in
                        self.params["layers"].items() if n.endswith("_q")] \
                + [("head", self.params["head_q"])]:
            # per-shard output width for the column-sharded pools
            sharded = name in ("wq", "wk", "wv") \
                or (name == "head" and self._shard_head)
            n_out = q.shape[-1] // (self.tp if sharded else 1)
            k_in = q.shape[-2] * (2 if wdt == "int4" else 1)
            why = _qm.ineligible(k_in, n_out, wdt)
            if why is not None:
                self._qmm_refused[name] = why
        if not self._qmm_refused:
            return kernel
        return kernel + "; xla-fake-quant for " + ", ".join(
            f"{n} ({w})" for n, w in sorted(self._qmm_refused.items()))

    def _weight_ops(self):
        """(mm, embed, head_logits) for the step bodies, resolved once
        per program build.

        f32 engines get the literal dense expressions, but for the
        leaves the engine holds [L, heads, d, in] (``_out_major``): the
        same sum, contracted on the weight's last axis, [Tq, heads, d]
        as it comes; quantized engines route
        every projection/MLP/head matmul by ``matmul_path``: through the
        fused dequant-matmul kernel where it runs and claims the weight,
        through its term-identical XLA fake-quant reference elsewhere —
        the same split-contract the paged attention kernel keeps."""
        dt = self._act_dtype
        wdt = self.weight_dtype
        if wdt != "float32":
            use_qmm = self.matmul_path.startswith("pallas")
            refused = self._qmm_refused

            def mm(h, p, name):
                q, s = p[name + "_q"], p[name + "_s"]
                if use_qmm and name not in refused:
                    out = _qm.matmul(h, q, s, weight_dtype=wdt)
                else:
                    out = _qm.reference_matmul(h, q, s, wdt)
                return out.astype(h.dtype)

            def embed(params, toks):
                return _qm.dequantize_rows(
                    jnp.take(params["embed_q"], toks, axis=0),
                    jnp.take(params["embed_s"], toks), wdt).astype(dt)

            def head_logits(params, hsel):
                q, s = params["head_q"], params["head_s"]
                if use_qmm and "head" not in refused:
                    return _qm.matmul(hsel.astype(jnp.float32), q, s,
                                      weight_dtype=wdt)
                return _qm.reference_matmul(hsel, q, s, wdt)
        else:
            out_major = self._out_major

            def mm(h, p, name):
                if name in out_major:
                    w = p[name]
                    return lax.dot_general(
                        h, w, (((h.ndim - 1,), (w.ndim - 1,)), ((), ())))
                return h @ p[name]

            def embed(params, toks):
                return jnp.take(params["embed"], toks, axis=0)

            def head_logits(params, hsel):
                if "head" not in params:
                    # a tied head: the embedding [V, H], contracted on H
                    return lax.dot_general(
                        hsel.astype(jnp.float32),
                        params["embed"].astype(jnp.float32),
                        (((1,), (1,)), ((), ())))
                return (hsel.astype(jnp.float32)
                        @ params["head"].astype(jnp.float32))
        return mm, embed, head_logits

    # ------------------------------------------------------------------
    # tensor-parallel layout (tp > 1)
    # ------------------------------------------------------------------

    def _param_specs(self) -> dict:
        """PartitionSpec pytree for decode_params under the 1-D tp mesh.

        q/k/v projections shard along their HEAD axis (leading L axis
        from the per-layer stack, then heads, head_dim, hidden: the
        engine's copy holds them [L, heads, d, in],
        ``_hold_out_major``) — each shard computes its contiguous head
        block with the full replicated activation, so no contraction is
        ever split and greedy outputs stay byte-identical to tp=1.  wo,
        the MLP, and the norms replicate; the unembedding column-shards
        over vocab only when it divides evenly.

        Quantized engines shard the SAME axes: a quantized pool slices
        along its output-column axis exactly like the f32 weight it
        replaced, and its scales slice with it (int8 scales are
        per-output-column; int4 scales keep a leading row-group axis),
        so tp=N never reshards or requantizes.
        """
        layers = {k: P() for k in self.params["layers"]}
        if self.weight_dtype == "float32":
            for k in self._out_major:
                layers[k] = P(None, "tp")
            return {"layers": layers, "embed": P(), "norm_f": P(),
                    "head": P(None, "tp") if self._shard_head else P()}
        for k in ("wq_q", "wk_q", "wv_q"):
            layers[k] = P(None, None, "tp")
        scale_cols = P(None, "tp") if self.weight_dtype == "int8" \
            else P(None, None, "tp")
        for k in ("wq_s", "wk_s", "wv_s"):
            layers[k] = scale_cols
        out = {"layers": layers, "embed_q": P(), "embed_s": P(),
               "norm_f": P()}
        if self._shard_head:
            out["head_q"] = P(None, "tp")
            out["head_s"] = P("tp") if self.weight_dtype == "int8" \
                else P(None, "tp")
        else:
            out["head_q"] = out["head_s"] = P()
        return out

    def _shard_params(self, params) -> dict:
        # specs lead the map (a PartitionSpec is itself a tuple pytree,
        # so it must be the is_leaf-guarded side)
        return jax.tree_util.tree_map(
            lambda s, x: jax.device_put(x, NamedSharding(self._mesh, s)),
            self._param_specs(), params,
            is_leaf=lambda x: isinstance(x, P))

    def _wrap_tp(self, run, n_host_args: int, n_front: int | None = None):
        """shard_map a step program's body over the tp mesh (identity at
        tp=1).

        KV/scale pools shard along their H_kv axis; params follow
        ``_param_specs``; the ``n_host_args`` trailing host-packed
        operands (tokens, cu_seqlens, kv_lens, block tables, logit
        index, sampling pytree — plus the fresh-page mask in int8 mode)
        replicate, a single P() covering each pytree by prefix.  Every
        one of the ``n_front`` non-pool outputs (default, the ragged
        step's: sampled tokens, the vector the host reads and, with a
        drafter, logits; the window's one packed vector) is genuinely
        replicated after the in-step all-gathers, so its out_spec is P().

        check_vma=False: the body mixes replicated and sharded operands
        and resolves them with explicit all-gathers, the same contract
        as the auto-parallel tier's cached psum programs.
        """
        if self.tp == 1:
            return run
        if n_front is None:
            n_front = 2 + (self._with_logits or self._tail_apart)
        kv = P(None, None, "tp")
        pools = (kv,) * len(self._pools())
        return shard_map(
            run, mesh=self._mesh, check_vma=False,
            in_specs=(self._param_specs(), *pools) + (P(),) * n_host_args,
            out_specs=(P(),) * n_front + pools)

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------

    def add_request(self, prompt, max_new_tokens: int = 32,
                    temperature: float = 0.0, eos_token_id=None,
                    seed: int = 0, top_k: int = 0, top_p: float = 1.0,
                    repetition_penalty: float = 1.0,
                    spec_k: int | None = None, generated=None,
                    on_token=None, on_finish=None, sink=None) -> int:
        """Queue one generation request; returns its rid.

        What the request emits goes to ``on_token(rid, token)`` and
        ``on_finish(RequestOutput)`` as it is committed, or, where the
        caller hands in a ``sink`` instead, to ONE call a launch:
        ``sink([(rid, tokens, output), ...])`` at the end of the commit,
        with every entry of the requests that share the sink in row
        order (``tokens`` a row's emissions, ``output`` None; a finished
        request's ``((), RequestOutput)`` follows its last token).  What
        ends outside a commit (``abort``) reaches the sink at once.

        ``generated`` re-admits a request that already emitted tokens
        (the runner's crash-recovery replay): the request enters exactly
        as a preempted sequence would — prefill covers prompt+generated,
        ``max_new_tokens`` still counts from the ORIGINAL prompt — so
        with the same seed the continuation is byte-identical to the
        uninterrupted run (sampling keys derive from (seed,
        len(generated)), and the prefix cache makes the re-prefill
        cheap when the old engine's pages survived).
        """
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        generated = [int(t) for t in (generated or [])]
        if len(generated) >= int(max_new_tokens):
            raise ValueError(
                f"continuation already holds {len(generated)} of "
                f"max_new_tokens={max_new_tokens} tokens")
        if len(prompt) + int(max_new_tokens) > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_model_len "
                f"({self.max_model_len})")
        if not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if int(top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if float(repetition_penalty) <= 0.0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {repetition_penalty}")
        if spec_k is None:
            spec_k = self.spec_k
        spec_k = min(int(spec_k), self.max_spec_k) \
            if self.drafter is not None else 0
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      tokens=list(prompt) + generated,
                      generated=list(generated),
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature),
                      eos_token_id=eos_token_id, seed=int(seed),
                      top_k=int(top_k), top_p=float(top_p),
                      repetition_penalty=float(repetition_penalty),
                      spec_k=spec_k, t_arrival=time.perf_counter(),
                      on_token=on_token, on_finish=on_finish, sink=sink)
        if req.repetition_penalty != 1.0:
            req.seen = np.zeros((self.config.vocab_size,), bool)
            req.seen[prompt] = True
            req.seen[generated] = True
        self._waiting.append(req)
        fl = self.flight
        if fl is not None:
            fl.open(rid, prompt_tokens=len(prompt),
                    t_submit=req.t_arrival)
        tr = self.tracer
        if tr is not None:
            tr.async_begin("req", f"{self._trace_track}:{rid}",
                           args={"rid": rid,
                                 "prompt_tokens": len(prompt),
                                 "replayed": len(generated),
                                 "max_new_tokens": int(max_new_tokens)})
            tr.instant("request.queued", track=self._trace_track,
                       args={"rid": rid, "step": self.launches + 1})
        return rid

    def has_unfinished(self) -> bool:
        # an in-flight launch still owes its completion even when every
        # queue is empty — run() drains the pipeline through it; same
        # for outputs an abort() flush buffered for the next step()
        return bool(self._waiting or self._running
                    or self._inflight is not None
                    or self._pending_finished)

    def abort(self, request_id: int, finish_reason: str = "aborted"):
        """Retire a request before it finishes — the client disconnected,
        its deadline passed, or the server is shedding it.

        Works at ANY point of the request's lifetime as observed between
        steps: still queued (nothing allocated), mid-chunked-prefill
        (pages for the already-prefilled prefix are live, resume state in
        ``req.cached``), mid-decode, or mid-speculation (the post-verify
        ``truncate`` already rolled back rejected drafts, so pool state
        is consistent at every step boundary).  Pages return through
        ``BlockManager.release`` — the abort-hardened path that only
        DECREFS pages shared with live neighbours (their chain hashes
        survive, so aborting one reader of a hot system prompt never
        evicts it) and never registers the aborted tail.

        Returns the partial RequestOutput, or None when request_id is
        unknown or already finished (an abort racing a natural finish is
        a benign, COUNTED no-op — ``stats.abort_noops`` — never an
        error).  Must be called from the engine's stepping thread,
        between steps — the frontend's EngineRunner queues cross-thread
        aborts and applies them at the next step boundary.
        """
        # flush the pipeline first: an in-flight launch may hold this
        # very request as a packed row, and completing it leaves pool
        # and queues in the consistent between-steps state the abort
        # paths (and their callers) assume.  The victim's own rows are
        # DROPPED unapplied — the caller decided to abort against the
        # state it could observe (tokens through the last completed
        # step), so the in-flight step's token for this request is
        # discarded and the abort output reports exactly the observable
        # prefix, same as a synchronous abort.  Other rows commit and
        # retire as usual; their outputs surface from the next step().
        if self._inflight is not None:
            self._complete(self.tracer, self._pending_finished,
                           drop_rid=request_id)
        req = None
        for r in self._running:
            if r.rid == request_id:
                req = r
                self._leave_running(r)
                break
        else:
            for i, r in enumerate(self._waiting):
                if r.rid == request_id:
                    req = r
                    del self._waiting[i]
                    break
        if req is None:
            self.stats.record_abort_noop()
            return None
        # a waiting request normally holds no pages — unless it was
        # preempted after generating (pages freed then) or never admitted
        # (never allocated); release() covers the running/mid-prefill case
        if self.blocks.has(req.rid):
            self.blocks.release(req.rid)
        if self.drafter is not None:
            self.drafter.release(req.rid)
        out = RequestOutput(rid=req.rid, prompt=list(req.prompt),
                            generated=list(req.generated),
                            finish_reason=finish_reason)
        if self.retain_outputs:
            self._finished[req.rid] = out
        self.stats.record_abort(finish_reason)
        if self.stats.windows is not None:
            self.stats.record_finish_quality(False)
            self.stats.record_request_latency(
                time.perf_counter() - req.t_arrival)
        fl = self.flight
        if fl is not None:
            fl.finished(req.rid, reason=finish_reason,
                        generated=len(req.generated),
                        tier=self._tier())
        tr = self.tracer
        if tr is not None:
            tr.async_end("req", f"{self._trace_track}:{req.rid}",
                         args={"finish_reason": finish_reason,
                               "generated": len(req.generated)})
        self._notify_finish(req, out)
        return out

    def _notify_tokens(self, req, toks) -> None:
        if req.sink is not None:
            self._emit(req, [int(t) for t in toks], None)
        elif req.on_token is not None:
            for t in toks:
                req.on_token(req.rid, int(t))

    def _notify_finish(self, req, out) -> None:
        if req.sink is not None:
            self._emit(req, (), out)
        elif req.on_finish is not None:
            req.on_finish(out)

    def _emit(self, req, toks, out) -> None:
        """File one entry for ``req``'s sink: with the launch being
        committed, whose sinks ``_hand_over`` calls once each at the
        commit's end, or, outside a commit (an abort), at once."""
        entry = (req.rid, toks, out)
        if self._emits is None:
            req.sink([entry])
        else:
            self._emits.setdefault(req.sink, []).append(entry)

    def _hand_over(self) -> None:
        """The end of a commit: every sink gets what the launch emitted
        for its requests, in row order, in ONE call."""
        emits, self._emits = self._emits, None
        for sink, launch in emits.items():
            sink(launch)

    def _row_calls(self) -> tuple:
        """What a committed row calls: (the stream callbacks, the retire
        check, the prefix cache's commit of a chunk, and of a decode
        token).  ``_split``, set by the commit under way, is None (no
        tracer: the bare methods, so a row pays nothing for the split)
        or a list whose entries grow by the nanoseconds inside them: [0]
        the callbacks (and, at the commit's end, the sinks' hand-over),
        [1] both cache commits, [2] the retire check
        (``engine.sample_commit``'s ``notify_ns``, ``cache_ns``,
        ``retire_ns``)."""
        calls = (self._notify_tokens, self._maybe_retire,
                 self.blocks.commit_prefill,
                 self.blocks.commit_decode_token)
        split = self._split
        if split is None:
            return calls
        return tuple(_timed(f, split, i)
                     for f, i in zip(calls, (0, 2, 1, 1)))

    @property
    def num_decode_programs(self) -> int:
        """Ragged programs at the decode-sized bucket (Tq == max_num_seqs)."""
        return sum(1 for Tq in self._ragged_progs
                   if Tq <= self.max_num_seqs)

    @property
    def num_prefill_programs(self) -> int:
        """Ragged programs at prefill-sized buckets (Tq > max_num_seqs)."""
        return sum(1 for Tq in self._ragged_progs
                   if Tq > self.max_num_seqs)

    def precompile_buckets(self) -> tuple:
        """Register the ragged-launch program for every reachable
        flat-token bucket, so no jit build ever lands inside the
        serving path.  The ladder is closed-form from the launch
        geometry: the decode-sized bucket, the speculation tier when a
        drafter is attached, and every prefill_token_bucket multiple up
        to the worst packable launch (a full max_prefill_tokens chunk
        budget plus every running row's tokens).  Idempotent; returns
        the ladder.  ``compile_counts`` lands at the ladder size and —
        because every later launch hits a registered bucket — stays
        there for the engine's whole life, which is what lets an A/B
        harness assert that a code path under test (e.g. the KV spill
        tier's restores) introduced no programs of its own."""
        tb = self.prefill_token_bucket
        ceiling = self.max_prefill_tokens + self._Lq
        ladder = {self.max_num_seqs}
        if self._with_logits and self.max_num_seqs < self._Lq < tb:
            ladder.add(self._Lq)
        ladder.update(range(tb, (-(-ceiling // tb) + 1) * tb, tb))
        for Tq in sorted(ladder):
            self._get_ragged_prog(Tq)
        return tuple(sorted(ladder))

    def run(self) -> dict:
        """Drive step() until every queued request finishes.  Outputs by
        rid; the run's metrics (incl. cache hits/misses, CoW copies,
        evictions, chunked-prefill queue depth) are in ``summary()``."""
        while self.has_unfinished():
            self.step()
        return dict(self._finished)

    def _resolve_tuning(self) -> dict:
        """Consult the kernel tuning cache once for this engine's launch
        geometry — per registered kernel: the bucket key queried, the
        config chosen, and whether a cache entry (exact or nearest
        bucket) answered.  Lookups are pure host-side dict reads; the
        kernels re-resolve the same keys at trace time, so this report
        is the provenance of the geometry the programs actually run."""
        from ..tune import cache_path, device_kind, kernel_config_with_meta
        dt = jnp.dtype(self._act_dtype).name
        d = self._hd
        shapes = {
            "flash_attention": {
                "seq_q": self.max_model_len, "seq_k": self.max_model_len,
                "head_dim": d, "dtype": dt},
            "flash_attention_varlen": {
                "seq_q": self.max_prefill_tokens,
                "seq_k": self.max_model_len, "head_dim": d, "dtype": dt},
            "fused_norms": {
                "rows": self.max_prefill_tokens,
                "hidden": self.config.hidden_size, "dtype": dt},
            "paged_attention": {
                "tq": self.prefill_token_bucket,
                "kv_heads": self._kvh // self.tp, "head_dim": d,
                "page": self.block_size, "nblk": self.nblk,
                "dtype": self.kv_dtype},
        }
        if self._latent:
            shapes = {"fused_norms": shapes["fused_norms"]}
        if self.weight_dtype != "float32":
            # the decode-shaped MLP projection — the step's biggest
            # weight stream and the shape the sweep's llama-class
            # buckets answer for
            shapes["quant_matmul"] = {
                "m": self.max_num_seqs, "k": self.config.hidden_size,
                "n": self.config.intermediate_size,
                "dtype": self.weight_dtype}
        kernels = {}
        for name, shape in shapes.items():
            config, meta = kernel_config_with_meta(name, shape)
            self.stats.record_tuning(name, bool(meta["hit"]))
            kernels[name] = {"hit": bool(meta["hit"]),
                             "source": meta["source"], "config": config,
                             "key": meta["key"]}
        return {"path": cache_path(), "device": device_kind(),
                "kernels": kernels}

    def summary(self) -> dict:
        """One dict of serving metrics + block-pool state for this run."""
        out = self.stats.summary()
        out["block_pool"] = self.blocks.stats()
        if self.kv_tier is not None:
            out["kv_tier"] = self.kv_tier.stats()
        out["kv_dtype"] = self.kv_dtype
        out["tp"] = self.tp
        out["kv_bytes_resident"] = self.kv_bytes_resident()
        out["kv_bytes_resident_per_shard"] = \
            self.kv_bytes_resident_per_shard()
        out["weight_dtype"] = self.weight_dtype
        out["weight_bytes_resident"] = self.weight_bytes_resident()
        out["weight_bytes_resident_per_shard"] = \
            self.weight_bytes_resident_per_shard()
        out["peak_resident_seqs"] = self.peak_resident_seqs
        # counted where the work is launched (_launch_ragged, the window
        # drain): real against padded query tokens
        out["tokens_real"] = self.pad_stats["real"]
        out["tokens_padded"] = self.pad_stats["padded"]
        out["kv_pages_live"] = self.pad_stats["kv_pages"]
        # pages the launches' new tokens touched and those tokens, a
        # layer's: pages a token is what writing them costs where whole
        # pages move (``kv_page_write``): 1.0 on a decode-only launch,
        # near 1 / block_size on a chunk
        out["kv_write_pages"] = self.pad_stats["kv_write_pages"]
        out["kv_write_tokens"] = self.pad_stats["kv_write_tokens"]
        if self._windowed:
            # pages the launches' rows held in a window layer (against
            # kv_pages_live, what one table for all layers would hold),
            # and pages live sequences gave back as they moved on
            out["kv_pages_window"] = self.pad_stats["kv_pages_window"]
            out["window_pages_returned"] = self.blocks.window_returned
        if self._stateful:
            # slots sequences hold a state in now; rows a layer's scan
            # began from zeros (a sequence's first) and rows it scanned,
            # summed over launches
            out["state_slots"] = sum(self._slot_used)
            out["state_starts"] = self.pad_stats["state_starts"]
            out["state_rows"] = self.pad_stats["state_rows"]
        if self._index_topk:
            # (query, key) pairs the indexed layers' queries saw and
            # those they selected, a layer's, summed over launches
            out["index_keys_visible"] = self.pad_stats["index_keys_visible"]
            out["index_keys_selected"] = \
                self.pad_stats["index_keys_selected"]
        # launches in all, those dispatched before the launch in front
        # of them was committed, the others by why not, and the rows
        # such a launch held of a request that commit retired
        out["launches"] = self.launches
        # launches whose execution had ended when the host came to read
        # them: the copy to the host, started at dispatch, had its head
        # start and the host, not the chip, was the pace of that launch
        out["reads_ready"] = self.reads_ready
        out["launches_ahead"] = self.launches_ahead
        out["ahead_fallbacks"] = dict(self.ahead_fallbacks)
        out["ahead_rows_dropped"] = self.ahead_rows_dropped
        # times a preemption made the scheduler filter rows it had
        # already taken against the running set again (0 while nothing
        # is preempted: the turn then walks each row once)
        out["sched_refilters"] = self.sched_refilters
        out["sample_launches"] = self.sample_stats["launches"]
        out["sample_chain_launches"] = self.sample_stats["chain_launches"]
        if self._has_experts:
            # token-expert pairs the held experts computed / routed to
            # any expert, held experts that got a token (summed over
            # layers and steps), and the most tokens one held expert
            # got in one layer of one step
            out.update(self.moe_counts)
            # (layer, expert) pairs held here: what a launch's
            # moe_experts_touched is a share of
            out["moe_experts_held"] = self._experts_held
        out["paths"] = self.paths()
        out["tuning_cache"] = {
            "path": self._tuning_report["path"],
            "device": self._tuning_report["device"],
            "kernels": {k: dict(v) for k, v in
                        self._tuning_report["kernels"].items()},
        }
        return out

    def paths(self) -> dict:
        """Where this engine computes and through which implementations:
        platform, device kind and devices, the attention and matmul
        paths, and the paths of every step program built so far.  Safe
        from the HTTP thread: the record is copied in one step (a build
        on the engine thread may insert meanwhile) and its entries are
        never mutated."""
        return {"platform": self._platform,
                "device_kind": self.devices[0].device_kind,
                "devices": [str(d) for d in self.devices],
                "attention": self.attention_path,
                "matmul": self.matmul_path,
                "programs": dict(self.program_paths)}

    # the pools an engine may hold, in the order a program takes them:
    # those under the block table, then the window table's
    # and last what a sequence keeps by batch slot (a state-space model)
    _POOLS = ("_kc", "_vc", "_ks", "_vs", "_ki", "_kw", "_vw", "_sc", "_ss")

    def _pools(self) -> tuple:
        """The page pools every program takes after the parameters and
        gives back: K and V (over int8 pages their scale pools too), or
        the one latent pool (and an indexer's keys beside it), each
        [L, num_blocks, ...]; with window layers the global layers'
        pools, then the window layers' [Lw, Nw, ...]; with state-space
        layers, last, what a sequence keeps by batch slot
        [Ls, max_num_seqs + 1, ...]: the convolution's last inputs and
        the scan's state."""
        return tuple(getattr(self, n) for n in self._POOLS
                     if getattr(self, n) is not None)

    def _set_pools(self, pools) -> None:
        names = [n for n in self._POOLS if getattr(self, n) is not None]
        for n, x in zip(names, pools):
            setattr(self, n, x)

    def kv_page_bytes(self) -> int:
        """MESH-TOTAL device bytes one KV page costs, by the pools' own
        shapes: every pool's slab of one page across every layer (K and
        V, plus the page's scale rows in int8 mode; or the latent rows),
        summed over every tp shard.  With window layers: a page of the
        block table's pools, the global layers' (the window layers'
        pages are counted as they are held: ``kv_bytes_resident``)."""
        return sum(x.size // x.shape[1] * np.dtype(x.dtype).itemsize
                   for x in (self._kc, self._vc, self._ks, self._vs,
                             self._ki) if x is not None)

    def _window_bytes_resident(self) -> int:
        """Bytes of the window layers' pages that sequences hold."""
        if not self._windowed:
            return 0
        return self.blocks.num_window_used * sum(
            x.size // x.shape[1] * np.dtype(x.dtype).itemsize
            for x in (self._kw, self._vw) if x is not None)

    def kv_page_bytes_per_shard(self) -> int:
        """Bytes one KV page costs ON ONE CHIP.  Pools shard along the
        H_kv axis (tp divides kvh, so page and scale slabs split
        exactly) — per-chip HBM is the binding capacity constraint, so
        pool sizing and pressure thresholds must use this figure under
        tp, not the mesh total."""
        return self.kv_page_bytes() // self.tp

    def kv_bytes_resident(self) -> int:
        """Device bytes holding real KV content: pages backing live
        sequences plus parked prefix pages (retained in HBM precisely so
        a prefix hit skips recompute; ``evict_parked`` reclaims them).
        Mesh-total under tp; the per-chip figure is
        ``kv_bytes_resident_per_shard``."""
        return ((self.blocks.num_used + self.blocks.num_cached)
                * self.kv_page_bytes()) + self._window_bytes_resident()

    def kv_bytes_resident_per_shard(self) -> int:
        """Resident KV bytes on ONE chip of the tp mesh (equals the
        mesh total at tp=1) — the number a per-chip HBM budget or
        DegradationController threshold should be compared against."""
        return ((self.blocks.num_used + self.blocks.num_cached)
                * self.kv_page_bytes_per_shard()) \
            + self._window_bytes_resident()

    def weight_bytes_resident(self) -> int:
        """MESH-TOTAL device bytes holding the decode weights: the
        quantized pools + their f32 scales + the f32 norms (or the full
        f32 tree for weight_dtype='float32').  The other half of
        resident HBM next to ``kv_bytes_resident`` — int8 pools land
        ~4x under f32, int4 ~8x."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.params):
            total += int(np.prod(np.shape(leaf))) \
                * np.dtype(leaf.dtype).itemsize
        return total

    def weight_bytes_resident_per_shard(self) -> int:
        """Resident weight bytes on ONE chip of the tp mesh: sharded
        leaves (q/k/v pools + scales, and the head when vocab divides)
        contribute 1/tp of their mesh total, replicated leaves their
        full size — the per-chip HBM figure budgets compare against."""
        if self.tp == 1:
            return self.weight_bytes_resident()
        total = 0

        def add(spec, x):
            nonlocal total
            b = int(np.prod(np.shape(x))) * np.dtype(x.dtype).itemsize
            sharded = any(a is not None for a in spec)
            total += b // self.tp if sharded else b
            return x

        jax.tree_util.tree_map(add, self._param_specs(), self.params,
                               is_leaf=lambda x: isinstance(x, P))
        return total

    @property
    def degradation_tier_entries(self) -> int:
        """Escalating degradation-controller transitions (0 when no
        pressure controller is installed)."""
        return 0 if self.pressure is None else self.pressure.tier_entries

    def _pool_structs(self, placed: bool = False) -> tuple:
        """ShapeDtypeStructs of what every program takes after the
        parameters: the page pools, then in int8 mode the scale pools
        (the fresh-page mask of the step programs is not among them).
        ``placed``: with the live arrays' shardings, so that lowering
        from the shapes gives the program the engine runs."""
        return tuple(jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if placed else None)
            for x in self._pools())

    def _step_head_structs(self, placed: bool = False) -> tuple:
        """(params, pools..., fresh mask in int8 mode) as shapes: the
        leading arguments of both step programs."""
        sds = jax.ShapeDtypeStruct
        params = jax.tree_util.tree_map(
            lambda x: sds(np.shape(x), x.dtype, sharding=getattr(
                x, "sharding", None) if placed else None), self.params)
        head = (params,) + self._pool_structs(placed)
        if self.kv_dtype == "int8":
            head += (sds((self._kc.shape[1],), jnp.bool_),)
        return head

    def _ragged_arg_structs(self, Tq: int, placed: bool = False) -> tuple:
        """The ragged step program's arguments at token bucket ``Tq`` as
        ShapeDtypeStructs: what ``program_specs`` audits and what a test
        or ``program_scopes`` lowers, with nothing allocated or run."""
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        B = self.max_num_seqs
        # with a state each row's batch slot comes first
        slots = (sds((B,), i32),) if self._stateful else ()
        return self._step_head_structs(placed) + slots + (
            sds((Tq,), i32), sds((B + 1,), i32), sds((B,), i32),
            sds(self._bt_shape, i32), sds((self._Lq,), i32),
            samp_structs(self._Lq, self.config.vocab_size),
            # prev (the sampled tokens of the launch in front, where
            # they came back) and src
            sds((self._Lq,), i32,
                sharding=self._no_prev.sharding if placed else None),
            sds((Tq,), i32))

    def _window_arg_structs(self, placed: bool = False) -> tuple:
        """The decode-window driver's arguments as ShapeDtypeStructs:
        the [B]-wide carry seeds plus the per-row freeze/key inputs."""
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        B = self.max_num_seqs

        def seqs():
            return sds((B,), i32)

        return self._step_head_structs(placed) + (
            seqs(), seqs(), sds((B,), jnp.bool_), seqs(), seqs(), seqs(),
            sds((B, 2), jnp.uint32), sds((B + 1, self.nblk), i32),
            samp_structs(B, self.config.vocab_size))

    def program_scopes(self, buckets=None) -> dict:
        """{program name: {instruction name: {"op_name", "dot"}}} for
        the step programs: the ragged program of each of ``buckets``
        (default: those built so far) and the decode window's if it is
        built, under the names the jit was given (``ragged_step_t192``).

        The TPU's trace names a device operation by its HLO
        instruction ("fusion.199") and carries nothing of the
        ``jax.named_scope`` it ran under; the compiled module's text
        does (``op_name``).  This is the map between the two, for
        whoever reads a device trace (``benchmark/harness/scopes.py``;
        which scope and kernel names an architecture's programs carry is
        listed in its ``benchmark/shapes/<name>.py``).
        Built only when asked: each program is lowered from shapes and
        compiled again, which the persistent compilation cache turns
        into a read; nothing here runs in a serving step or in set-up."""
        if buckets is None:
            buckets = sorted(self._ragged_progs)
        out = {}
        for Tq in buckets:
            compiled = self._get_ragged_prog(Tq).lower(
                *self._ragged_arg_structs(Tq, placed=True)).compile()
            out[f"ragged_step_t{Tq}"] = _instruction_scopes(
                compiled.as_text())
        if self._window_prog is not None:
            compiled = self._window_prog.lower(
                *self._window_arg_structs(placed=True)).compile()
            out[f"decode_window_k{self.decode_window}"] = \
                _instruction_scopes(compiled.as_text())
        return out

    def program_specs(self, *, large_bytes: int = 1 << 20) -> list:
        """Every program this engine compiles, as analysis ProgramSpecs.

        Arguments are ShapeDtypeStructs (nothing allocates or runs) and
        donate_argnums is the INTENDED device donation — the analyzer
        audits the TPU contract even when the process runs on CPU, where
        the builders drop donation.  ``graftlint --audit-serving`` and
        tests/test_serving_audit.py consume this.
        """
        from ..analysis import ProgramSpec

        sds = jax.ShapeDtypeStruct
        dt = self._act_dtype
        declared = dt if np.dtype(dt).name in ("bfloat16", "float16") \
            else None
        # representative token bucket: the smallest prefill-sized launch
        # (every other bucket traces the same fn at another Tq)
        Tq = max(self.prefill_token_bucket, self.max_num_seqs)

        rag_fn, rag_donate = self._make_ragged_fn(Tq)
        cow_fn, cow_donate = self._make_cow_fn()
        # a tp>1 engine compiles the SAME program kinds laid over the
        # mesh; the suffix keeps its audit entries distinct in reports.
        # Weight-quantized engines likewise keep the same kinds with a
        # dequant routed through the fused kernel path — their suffix
        # keeps the regenerated serving report's names collision-free
        # against the f32 engine's.  The quantized step threads the
        # scale pools (donated along with the page pools) plus the
        # per-launch fresh-page mask.
        q8 = "_q8" if self.kv_dtype == "int8" else ""
        sfx = q8 + {"int8": "_w8", "int4": "_w4"}.get(self.weight_dtype, "")
        sfx += f"_tp{self.tp}" if self.tp > 1 else ""
        out = [
            ProgramSpec(
                "serving.ragged_step" + sfx, rag_fn,
                self._ragged_arg_structs(Tq),
                donate_argnums=rag_donate, declared_dtype=declared,
                large_bytes=large_bytes),
            ProgramSpec(
                "serving.cow_copy" + sfx, cow_fn,
                self._pool_structs() + (sds((), jnp.int32),
                                        sds((), jnp.int32)),
                donate_argnums=cow_donate, declared_dtype=declared,
                large_bytes=large_bytes),
        ]
        if self.decode_window > 1:
            win_fn, win_donate = self._make_window_fn()
            out.append(ProgramSpec(
                "serving.decode_window" + sfx, win_fn,
                self._window_arg_structs(),
                donate_argnums=win_donate, declared_dtype=declared,
                large_bytes=large_bytes))
        if self._tail_apart:
            V = self.config.vocab_size
            out.append(ProgramSpec(
                "serving.sampled_tail" + sfx, _sampled_tail,
                (sds((self._Lq, V), jnp.float32),
                 samp_structs(self._Lq, V),
                 sds((2 * self._Lq + 4 * self._has_experts,), jnp.int32)),
                declared_dtype=None, large_bytes=large_bytes))
        return out

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------

    # What the scheduler reads of a row is its state once the launch in
    # flight has been committed: known before that launch returns,
    # except for the id of the token it samples.  ``req.inflight`` is 0
    # for every row when nothing is in flight, and these are then the
    # committed state itself.

    def _pos(self, req) -> int:
        """Positions of req whose KV is in the pool, the launch in
        flight counted."""
        return req.cached + req.inflight

    def _ngen(self, req) -> int:
        """Tokens req has generated, the one the launch in flight samples
        for it counted (a decode row's, a chunk's that ends its prompt)."""
        return len(req.generated) + (
            req.inflight > 0
            and req.cached + req.inflight >= len(req.tokens))

    def _decode_ready(self, req) -> bool:
        """Prefill complete and exactly the last generated token's KV is
        still unwritten (the decode step writes it and samples the next)."""
        pos = self._pos(req)
        return (pos >= len(req.tokens)
                and pos == len(req.prompt) + self._ngen(req) - 1)

    def _leaving(self, req) -> bool:
        """The launch in flight samples req's last token: its commit
        retires it whatever the token is (a stop token's id is not known
        ahead, ``max_new_tokens`` is)."""
        return req.inflight > 0 and self._ngen(req) >= req.max_new_tokens

    def step(self) -> list:
        """One engine iteration.  With ``overlap`` on (the default) this
        is one turn of the dispatch/completion PIPELINE: the launch the
        previous call left in flight is still on the device, so this
        call first schedules, packs and launches the NEXT one from the
        state that launch will leave (dispatch ahead: the sampled tokens
        pass from one launch to the next on the device), and only then
        blocks on the first and commits it.  Where the next launch needs
        something only the commit can give (``_ahead_blocked``, and the
        reasons ``_dispatch`` abandons for) the call commits first and
        dispatches after, as a synchronous step does.  Returns the
        requests that finished: under overlap these are the completions
        of the PREVIOUS call's dispatch (the pipeline's one-step
        latency).  With ``overlap`` off the dispatch completes in the
        same call and the step is the classic synchronous admit ->
        schedule -> launch -> apply -> retire iteration.

        With a tracer installed every phase lands in the step timeline
        (dispatch: admit where somebody waited / schedule, which
        runs on over the packing of the rows it chose / device launch;
        complete: block-on-result / sample-commit / retire; plus the
        device in-flight window), each with the id of the launch it
        belongs to (``step``: see docs/observability.md); with none the
        phase seams are single attribute checks."""
        # ONE call site into _step, tracer or none: the line a program
        # is first reached from must not depend on who is watching
        tr = self.tracer
        t0 = time.perf_counter_ns()          # the tracer's clock too
        self._blocked_ns = 0
        if tr is not None:
            sid = self.launches + 1
        finished = self._step(tr)
        # the turn: this call's work on the engine thread, which is its
        # wall time less what _complete spent waiting on the chip
        self.stats.record_turn(time.perf_counter_ns() - t0
                               - self._blocked_ns)
        if tr is not None:
            tr.complete("engine.step", t0, track=self._trace_track,
                        args={"step": sid, "finished": len(finished)})
        return finished

    def _step(self, tr) -> list:
        # outputs that finished inside an abort()'s pipeline flush
        # surface here, so the step()-return channel never drops one
        finished = self._pending_finished
        self._pending_finished = []
        why = "idle" if self.overlap else "sync"
        ahead = None
        if self._inflight is not None:
            # the launch from the previous step() call is (possibly)
            # still running on-device: the next one goes behind it on
            # the device's queue first, and the host's whole turn runs
            # INSIDE that window; then block on the ticket and commit
            why = self._ahead_blocked(self._inflight)
            if why is None:
                try:
                    ahead = self._dispatch(tr, ahead=self._inflight)
                    # (nothing to launch yet: what the commit frees may
                    # give the dispatch below something)
                    why = "idle"
                except _AheadAbandoned as e:
                    why = e.reason
            self._queued = ahead
            self._complete(tr, finished)
            self._queued = None
        if ahead is None:
            if self.kv_tier is not None:
                # step boundary: no launch is in flight (completion
                # above materialized the pools), and restores land
                # before this step's admission packs only the residual
                # prefill suffix
                self._drain_kv_tier(tr)
            ahead = self._dispatch(tr, why=why)
        self._inflight = ahead
        if ahead is not None:
            self._ride(ahead, True)
            if not self.overlap:
                self._complete(tr, finished)

        ev = self.blocks.eviction_count
        if ev != self._evictions_seen:
            self.stats.record_evictions(ev - self._evictions_seen)
            self._evictions_seen = ev
        return finished

    def _ride(self, ticket, on: bool) -> None:
        """Count ``ticket``'s positions as in flight on its rows (or, at
        its commit, no longer).  A verify row's and a window's are not
        known ahead and not counted: nothing is dispatched ahead of
        either (``_ahead_blocked``)."""
        if ticket.window:
            return
        for req, n in ticket.chunks:
            req.inflight = n if on else 0
        for req in ticket.batch:
            req.inflight = int(on)

    def _ahead_blocked(self, ticket):
        """Why the next launch cannot be dispatched before ``ticket`` is
        committed, from what the engine is and holds; None where it can
        be tried.  Each of these needs the step boundary (nothing in
        flight, every token on the host): a decode window advances its
        rows by a count the host learns at the drain; draft acceptance
        reads the logits on the host; the spill tier copies pages
        between launches; the pressure signal and an armed fault plan
        are defined against the synchronous step."""
        if ticket.window:
            return "decode_window"
        if self.drafter is not None:
            return "drafter"
        if self.kv_tier is not None:
            return "kv_tier"
        if self.pressure is not None:
            return "pressure"
        if self.fault_plan is not None and self.fault_plan.armed():
            return "fault_plan"
        return None

    def _dispatch(self, tr, ahead=None, why: str = "idle"):
        """Admission + scheduling + packing + block-table staging + the
        ragged launch, WITHOUT materializing results: the device arrays
        come back in a ``_StepTicket`` (JAX async dispatch — nothing in
        this path forces a host sync on them), or None where there was
        nothing to launch.  ``_complete`` blocks on the ticket and
        commits.

        ``ahead`` is the ticket in flight when this runs BEFORE its
        commit: the scheduler then reads every row where that launch
        will leave it (``_pos``, ``_ngen``), a row whose next input is
        that launch's sample names its logit row in ``src`` and the
        program takes the token from ``ahead.sampled`` on the device,
        and nothing is preempted: a reservation or a page copy the pool
        cannot meet raises ``_AheadAbandoned``, as does a row with a
        repetition penalty (its ``seen`` mask needs the id on the host)
        and, with a decode window configured, a pure-decode launch (the
        window's).  ``why`` is the reason a launch made here is not
        ahead, for the counter and the trace."""
        self._ahead_of, self._not_ahead = ahead, why
        try:
            return self._dispatch_launch(tr)
        finally:
            self._ahead_of = None

    def _dispatch_launch(self, tr):
        plan = self.fault_plan
        if plan is not None:
            # fault seams fire BEFORE any scheduler mutation, so a crash
            # leaves queues and pool in the consistent between-steps
            # state recovery replays from.  advance() here keys the plan
            # step on DISPATCH order, which equals completion order (an
            # armed plan keeps the pipeline one deep: ticket N completes
            # before N+1 is dispatched), so a schedule means the same
            # thing overlap on or off.
            plan.advance()
            if plan.take_pool_entry():
                self.stats.record_fault("pool")
            slow = plan.take_slow()
            if slow > 0.0:
                self.stats.record_fault("slow")
                time.sleep(slow)
            if plan.take_crash():
                self.stats.record_fault("crash")
                raise InjectedFault(
                    f"injected step crash at plan step {plan.step}")

        if self.pressure is not None:
            prev_tier = self.pressure.state
            self.pressure.update(self.blocks)
            self.stats.set_degradation_state(self.pressure.state)
            if tr is not None and self.pressure.state != prev_tier:
                tr.instant("pressure.tier", track=self._trace_track,
                           args={"step": self.launches + 1,
                                 "from": prev_tier,
                                 "to": self.pressure.state,
                                 "name": _TIER_NAMES.get(
                                     self.pressure.state,
                                     str(self.pressure.state))})
            if self.pressure.evict_now:
                n = self.blocks.evict_parked(self.pressure.evict_batch)
                if n:
                    self.stats.record_parked_evictions(n)

        ahead = self._ahead_of
        if tr is not None:
            sid = self.launches + 1     # the launch this call prepares
            t_d = tr.now()
            t = tr.now()
            waited = len(self._waiting)
        admitted = self._admit()
        if admitted:
            self.stats.record_admission(len(admitted))
        if tr is not None and waited:
            # with nobody waiting there is no admission phase to show
            tr.complete("engine.admit", t, track=self._trace_track,
                        args={"step": sid, "admitted": len(admitted),
                              "running": len(self._running),
                              "waiting": len(self._waiting)})
        self.peak_resident_seqs = max(self.peak_resident_seqs,
                                      len(self._running))
        self.stats.record_prefill_queue(
            sum(1 for r in self._running if self._pos(r) < len(r.tokens))
            + len(self._waiting))

        if tr is not None:
            t = tr.now()
            ev0 = self.blocks.eviction_count
            rf0 = self.sched_refilters
            self._cow_n = self._cow_ns = 0
        chunks = spec = batch = ()
        ticket = None
        try:
            if ahead is not None and any(r.seen is not None
                                         for r in self._running):
                raise _AheadAbandoned("penalty")
            chunks = self._schedule_prefill_chunks()
            # the chunks stand as taken (their own victims dropped); the
            # rows below are taken from the running set as it is now
            pre0 = self.stats.preemptions

            # decode-ready set (chunk owners are still mid-prefill, so
            # the row classes are disjoint by construction)
            batch = [r for r in self._running
                     if self._decode_ready(r) and not self._leaving(r)]
            # speculative sequences pack a [last_token, drafts...]
            # window; everything else packs a single decode token in
            # the same launch
            spec, batch = self._split_spec(batch)
            spec, demoted = self._reserve_verify_pages(spec)
            batch.extend(demoted)
            if self.stats.preemptions != pre0:
                # verify reservation/CoW preempted plain-decode members
                self.sched_refilters += 1
                batch = [r for r in batch
                         if self._is_running(r) and self._decode_ready(r)]
            batch = self._reserve_decode_pages(batch)
            if self.stats.preemptions != pre0:
                # a reservation above preempted, and its victim may be a
                # chunk owner or an already-reserved row: re-filter each
                # class against the surviving running set before packing
                # the launch.  (A launch dispatched ahead never gets
                # here: it preempts nobody, it is abandoned.)
                self.sched_refilters += 1
                chunks = [(r, n) for r, n in chunks if self._is_running(r)]
                spec = [(r, d, q) for r, d, q in spec
                        if self._is_running(r)]
                batch = [r for r in batch if self._is_running(r)]
            batch.sort(key=lambda r: r.slot)
            if ahead is not None and self.decode_window > 1 \
                    and not chunks and batch:
                raise _AheadAbandoned("decode_window")
        except _AheadAbandoned as e:
            if tr is not None:
                tr.complete("engine.schedule", t, track=self._trace_track,
                            args={"step": sid, "abandoned": e.reason})
                tr.complete("engine.dispatch", t_d,
                            track=self._trace_track,
                            args={"step": sid, "launched": False,
                                  "ahead": True, "abandoned": e.reason})
            raise
        if tr is not None:
            sched = {"step": sid, "chunks": len(chunks),
                     "spec": len(spec), "decode": len(batch),
                     "evicted": self.blocks.eviction_count - ev0,
                     "cow": self._cow_n, "cow_ns": self._cow_ns,
                     "refilters": self.sched_refilters - rf0}
            if chunks or spec or batch:
                # the span runs on over the packing of what it chose
                self._sched_open = (t, sched)
            else:
                tr.complete("engine.schedule", t, track=self._trace_track,
                            args=sched)

        if chunks or spec or batch:
            t0 = time.perf_counter()
            if (self.decode_window > 1 and not chunks and not spec
                    and self._window_eligible(batch)):
                ticket = self._dispatch_window(batch, tr, t0)
            if ticket is None:
                sampled, packed, logits, spec_slices, chunk_slots, \
                    batch_slots = self._run_ragged(chunks, spec, batch)
                now = time.perf_counter()
                slot_of = {r.rid: s for (r, _), s
                           in zip(chunks, chunk_slots)}
                slot_of.update((r.rid, s)
                               for r, s in zip(batch, batch_slots))
                ticket = _StepTicket(
                    chunks=chunks, spec=spec, batch=batch,
                    sampled=sampled, packed=packed, logits=logits,
                    spec_slices=spec_slices, chunk_slots=chunk_slots,
                    batch_slots=batch_slots, dispatch_s=now - t0,
                    t_launch=now,
                    launch_ns=tr.now() if tr is not None else 0,
                    step=self.launches, inflight=self.overlap,
                    slot_of=slot_of)
        if tr is not None:
            tr.complete("engine.dispatch", t_d, track=self._trace_track,
                        args={"step": sid, "chunks": len(chunks),
                              "spec": len(spec),
                              "decode": len(batch),
                              "launched": ticket is not None,
                              "ahead": ahead is not None})
        return ticket

    def _complete(self, tr, finished: list, drop_rid=None) -> None:
        """Block on the in-flight ticket and commit it: ONE read of the
        packed vector the launch sent on its way at dispatch (sampled
        tokens, finiteness flags, expert counts: sliced here, on the
        host; the verify logits beside it where the launch holds spec
        rows), the NaN seam over the live rows, the step timing split
        into its dispatch/block halves, then apply + retire.  Whether
        the execution had ended when the host asked is counted
        (``reads_ready``) and said on ``engine.block_on_result``
        (``ready``).

        ``drop_rid`` (abort-while-in-flight) discards that request's
        packed rows unapplied: no token commit, no retirement, leaving
        the request holding exactly the tokens the aborting caller
        could observe.  The rows in ``ticket.dropped`` go the same way:
        their requests were retired by the commit in front of this
        launch, after it had been dispatched; their pages, which this
        launch still named, are given back here."""
        ticket = self._inflight
        self._inflight = None
        self._ride(ticket, False)
        plan = self.fault_plan
        if plan is not None and ticket.inflight:
            # completion-order seams: fire while the ticket is genuinely
            # in flight (overlap on), between launch and materialize —
            # the window a real device fault or host stall would hit
            slow = plan.take_inflight_slow()
            if slow > 0.0:
                self.stats.record_fault("inflight_slow")
                time.sleep(slow)
            if plan.take_inflight_crash():
                self.stats.record_fault("inflight_crash")
                raise InjectedFault(
                    f"injected in-flight crash at plan step {plan.step}")
        # what this commit emits belongs to the launch that computed it,
        # not to the step() call that happens to commit it
        sid = self._commit_step = ticket.step
        if tr is not None:
            t_c = tr.now()
            t = t_c
        # had the execution ended when the host got here?  Then the
        # copy the launch started has had its head start, and the host
        # was the pace of this launch
        ready = ticket.packed.is_ready()
        self.reads_ready += ready
        t0 = time.perf_counter_ns()
        host = np.asarray(ticket.packed)
        logits = np.asarray(ticket.logits) if ticket.spec else None
        block_ns = time.perf_counter_ns() - t0
        self._blocked_ns += block_ns
        block_s = block_ns / 1e9
        sampled, ok, counts = _unpack_results(
            host, (self.decode_window, self.max_num_seqs) if ticket.window
            else (self._Lq,))
        # ONE host round-trip per completion (one device-to-host read:
        # the packed vector), whether the launch carried a single step
        # or a whole K-token decode window — the ratio of this counter
        # to emitted tokens is the win the window buys
        self.stats.record_round_trip()
        if tr is not None:
            tr.complete("engine.block_on_result", t,
                        track=self._trace_track,
                        args={"step": sid, "ready": ready})
            if ticket.launch_ns and ticket.inflight:
                # X event spanning launch -> materialized: the window
                # host work can hide inside (step_timeline.py intersects
                # host-phase spans with these to report overlap ACHIEVED).
                # Synchronous tickets (overlap off, or the drain path)
                # emit no window: nothing host ran while they flew.
                tr.complete("engine.device_inflight", ticket.launch_ns,
                            track=self._trace_track,
                            args={"step": sid,
                                  "rows": len(ticket.chunks)
                                  + len(ticket.spec)
                                  + len(ticket.batch)})
            if self._queued is not None:
                # the launch queued behind this one has the device from
                # here, not from its own jit call: its window starts
                # where this one's ends, and no step is counted twice
                self._queued.launch_ns = tr.now()
        if ticket.window:
            # window outputs are [K, B]: the NaN seam corrupts one live
            # row's FIRST iteration (the device kept looping; the drain
            # quarantines at the poisoned step and drops the rest of
            # that row's column)
            ok0 = self._inject_nan(ok[0], list(ticket.batch_slots))
            if ok0 is not ok[0]:
                ok = np.array(ok)
                ok[0] = ok0
        else:
            ok = self._inject_nan(ok, ticket.chunk_slots
                                  + ticket.batch_slots
                                  + [o for o, _ in ticket.spec_slices])
        chunks, spec, batch = ticket.chunks, ticket.spec, ticket.batch
        chunk_slots = ticket.chunk_slots
        batch_slots = ticket.batch_slots
        spec_slices = ticket.spec_slices
        drop = set(ticket.dropped)
        if drop_rid is not None:
            drop.add(drop_rid)
        if drop:
            kc = [i for i, (r, _) in enumerate(chunks) if r.rid not in drop]
            chunks = [chunks[i] for i in kc]
            chunk_slots = [chunk_slots[i] for i in kc]
            ks = [i for i, (r, _, _) in enumerate(spec)
                  if r.rid not in drop]
            spec = [spec[i] for i in ks]
            spec_slices = [spec_slices[i] for i in ks]
            kb = [i for i, r in enumerate(batch) if r.rid not in drop]
            batch = [batch[i] for i in kb]
            batch_slots = [batch_slots[i] for i in kb]
        # the last launch that named these pages has come back
        for rid, how in ticket.dropped.items():
            getattr(self.blocks, how)(rid)
        self.ahead_rows_dropped += len(ticket.dropped)
        spec_ok = [bool(ok[o:o + n].all())
                   for o, n in spec_slices]
        spec_logits = None
        if spec:
            spec_logits = [logits[o:o + n]
                           for o, n in spec_slices]
        # dur is the engine's ACTIVE time on this launch (host packing +
        # the residual block); the device time hidden under the next
        # dispatch and the inter-call gap is exactly what the overlap
        # bought
        dur = ticket.dispatch_s + block_s
        self.stats.record_step(dur, dispatch_s=ticket.dispatch_s,
                               block_s=block_s)
        t = time.perf_counter_ns()
        # where the commit's time goes, by what a row calls (a tracer
        # installed; else the rows call the bare methods: _row_calls)
        split = self._split = None if tr is None else [0, 0, 0]
        self._emits = {}
        if ticket.window:
            self._apply_window(batch, batch_slots, sampled, ok, dur,
                               finished, ticket.window,
                               ticket.sample_chain)
        else:
            self._apply_ragged(chunks, spec, batch, sampled, ok, spec_ok,
                               spec_logits, chunk_slots, batch_slots,
                               dur, finished)
        counted = None
        if counts.size:
            mc = self.moe_counts
            counted = dict(zip(mc, map(int, counts)))
            for name, n in counted.items():
                mc[name] = max(mc[name], n) if name == "moe_load_max" \
                    else mc[name] + n
        # delivery is the commit's: commit_ns and notify_ns hold it whole
        t_h = time.perf_counter_ns()
        self._hand_over()
        if split is not None:
            split[0] += time.perf_counter_ns() - t_h
        self.stats.record_commit(time.perf_counter_ns() - t)
        if tr is not None:
            commit_args = {"step": sid, "finished": len(finished),
                           "rows": len(chunks) + len(spec) + len(batch),
                           "notify_ns": split[0], "cache_ns": split[1],
                           "retire_ns": split[2]}
            if counted is not None:
                commit_args.update(counted)
            tr.complete("engine.sample_commit", t,
                        track=self._trace_track, args=commit_args)
            tr.complete("engine.complete", t_c, track=self._trace_track,
                        args={"step": sid, "finished": len(finished)})

    def _invalidate_bt(self, rid: int) -> None:
        """Drop both decode buffers' staged block-table rows for rid.
        Called whenever a rid's staged table can go stale without a
        version bump: admission re-acquires reset the version counter,
        and preemption frees the table outright."""
        for buf in self._dbufs:
            buf.bt_ver.pop(rid, None)

    def _break_decode_layout(self) -> None:
        """Invalidate the decode fast path entirely: any mixed launch
        (and post-verify truncate) rewrites tables and row order, so
        both buffers full-restage at their next pure-decode launch."""
        for buf in self._dbufs:
            buf.layout = ()
            buf.bt_ver.clear()

    def _apply_ragged(self, chunks, spec, batch, sampled, ok, spec_ok,
                      spec_logits, chunk_slots, batch_slots, dur,
                      finished):
        """Advance every packed row from the launch's outputs: chunk rows
        commit their prefix (emitting a first token when the prompt
        completes), spec rows run host-side draft acceptance, decode rows
        emit one token.  A row whose logits came back non-finite is
        QUARANTINED before any of its state commits — the offending
        sequence retires with finish_reason="numerical_error" and its
        pages leave through the abort-hardened release path (never the
        cache-registering free path), so one poison row cannot spread
        through the prefix cache or take down its batchmates.  The
        launch duration splits across the stats channels pro-rata by
        packed tokens."""
        notify, retire, commit_chunk, commit_token = calls = \
            self._row_calls()
        chunk_tokens = sum(n for _, n in chunks)
        spec_tokens = sum(len(d) + 1 for _, d, _ in spec)
        total = max(chunk_tokens + spec_tokens + len(batch), 1)
        occ = len(self._running) / self.max_num_seqs
        tr = self.tracer

        done = 0
        for (req, n), s in zip(chunks, chunk_slots):
            if not ok[s]:
                self._quarantine(req, finished)
                continue
            req.cached += n
            if self.enable_prefix_caching:
                commit_chunk(req.rid, n)
            if tr is not None:
                tr.instant("request.prefill_chunk",
                           track=self._trace_track,
                           args={"rid": req.rid, "tokens": n,
                                 "done": req.cached >= len(req.tokens),
                                 "step": self._commit_step})
            fl = self.flight
            if fl is not None:
                fl.prefill_chunk(req.rid, n)
            if req.cached == len(req.tokens):
                done += 1
                tok = int(sampled[s])
                req.generated.append(tok)
                if req.seen is not None:
                    req.seen[tok] = True
                if len(req.generated) == 1:
                    ttft = time.perf_counter() - req.t_arrival
                    self.stats.record_ttft(ttft)
                    if fl is not None:
                        fl.first_token(req.rid, ttft)
                    if tr is not None:
                        tr.instant("request.first_token",
                                   track=self._trace_track,
                                   args={"rid": req.rid,
                                         "step": self._commit_step})
                notify(req, (tok,))
                retire(req, finished)
        if chunks:
            self.stats.record_prefill(dur * chunk_tokens / total,
                                      chunk_tokens, done)

        if spec:
            n_emitted = 0
            for i, ((req, drafts, qd), lg) in enumerate(
                    zip(spec, spec_logits)):
                if not spec_ok[i]:
                    self._quarantine(req, finished)
                    continue
                n_emitted += self._apply_spec_result(req, drafts, qd, lg,
                                                     finished, calls)
            self.stats.record_verify(dur * spec_tokens / total,
                                     n_emitted, occ)

        if batch:
            # before the rows' callbacks fire: a client that scrapes
            # /metrics on its stream's last frame finds its tokens counted
            self.stats.record_decode(dur * len(batch) / total,
                                     len(batch), occ)
        for req, s in zip(batch, batch_slots):
            if not ok[s]:
                self._quarantine(req, finished)
                continue
            if self.enable_prefix_caching:
                commit_token(req.rid, req.generated[-1])
            req.cached += 1
            tok = int(sampled[s])
            req.generated.append(tok)
            if req.seen is not None:
                req.seen[tok] = True
            notify(req, (tok,))
            retire(req, finished)

    # ------------------------------------------------------------------
    # device-resident decode window (decode_window > 1)
    # ------------------------------------------------------------------

    def _window_eligible(self, batch: list) -> bool:
        """True when this step's pack may run as a K-step device window:
        a STEADY pure-decode state — every runner decode-ready, nobody
        waiting for a slot (a window would delay their admission by up
        to K steps), and no row about to carry a verify window.  The
        caller already established there are no chunk/spec rows this
        step; the per-step path remains the universal fallback."""
        if not batch or self._waiting:
            return False
        if len(batch) != len(self._running):
            return False                # a runner is still mid-prefill
        if self.drafter is not None:
            for r in batch:
                if not r.spec_disabled and r.spec_k > 0:
                    return False        # next rounds pack verify rows
        return True

    def _reserve_window_pages(self, batch: list, k: int):
        """Pre-reserve each row's k tokens of page slack before the
        window launches (clamped to the row's remaining generation
        budget — a row the active-mask will freeze after m < k tokens
        writes only m positions).  All-or-nothing AT THIS k: a pool
        that cannot cover the whole window rolls every grow back and
        returns None — the dispatcher then retries at a smaller k'
        before surrendering to K=1; it NEVER preempts for a window.

        No copy-on-write resolution is needed here: the per-step
        reservation that already ran this dispatch privatized the page
        holding the first write position, and every page boundary the
        window crosses past it lands on a freshly allocated (private)
        page."""
        rows = []
        for req in batch:
            m = min(k, req.max_new_tokens - len(req.generated))
            rows.append((req.rid, req.cached + m))
        return self.blocks.reserve_window(rows)

    def _dispatch_window(self, batch: list, tr, t0: float):
        """Reserve, pack, and launch one K-step decode window over
        ``batch`` (slot-sorted, first-write pages already ensured).
        Returns the window's ticket, or None when the pool could not
        cover even a 2-token window (the caller runs the per-step path
        for this step).  Between those extremes the
        window ADAPTS: when K tokens of slack don't fit, the dispatch
        retries the reservation at K-1, K-2, ... and runs the largest
        feasible K' device-resident — the per-row generation budgets
        handed to the launch freeze every row after K' tokens, so the
        compiled driver (still built at static K) exits the while_loop
        early instead of the host surrendering the whole round-trip
        amortization."""
        K = self.decode_window
        kp = 0
        for k_try in range(K, 1, -1):
            if self._reserve_window_pages(batch, k_try) is not None:
                kp = k_try
                break
        if kp == 0:
            self.stats.record_window_fallback()
            if tr is not None:
                tr.instant("engine.window_fallback",
                           track=self._trace_track,
                           args={"step": self.launches + 1,
                                 "rows": len(batch), "k": K})
            return None
        if kp < K:
            self.stats.record_window_shrink()
            if tr is not None:
                tr.instant("engine.window_shrink",
                           track=self._trace_track,
                           args={"step": self.launches + 1,
                                 "rows": len(batch), "k": K, "kp": kp})
        B = self.max_num_seqs
        n = len(batch)
        toks = np.zeros((B,), np.int32)
        kvl = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        gen = np.zeros((B,), np.int32)
        budgets = np.zeros((B,), np.int32)
        eos_ids = np.full((B,), -1, np.int32)   # no token id is < 0, so
        base_keys = np.zeros((B, 2), np.uint32)  # -1 == "no eos" rows
        bt = np.full((B + 1, self.nblk), NULL_BLOCK, np.int32)
        samp = make_samp(B, self.config.vocab_size)
        if tr is not None:
            sid = self.launches + 1
            t = tr.now()
        for s, req in enumerate(batch):
            toks[s] = req.generated[-1]
            kvl[s] = req.cached + 1
            active[s] = True
            gen[s] = len(req.generated)
            # the K'-shrunk budget: the device active-mask freezes the
            # row after exactly kp tokens (kp == K leaves the row's own
            # generation budget in charge, same as before)
            budgets[s] = min(req.max_new_tokens,
                             len(req.generated) + kp)
            if req.eos_token_id is not None:
                eos_ids[s] = int(req.eos_token_id)
            self._fill_samp(samp, s, req)
            if req.temperature > 0.0:
                # the loop body re-derives fold_in(base, generated)
                # per iteration — the identical threefry derivation
                # _req_key performs host-side at K=1
                base_keys[s] = np.asarray(
                    jax.random.PRNGKey(req.seed), np.uint32)
        if tr is not None:
            t_bt = tr.now()
        for s, req in enumerate(batch):
            bt[s] = self.blocks.padded_table(req.rid, self.nblk)
        if tr is not None:
            self._packed(tr, t, t_bt, rows=n, tokens=n, bucket=B, window=kp)
        # the window grows tables past anything the per-step buffers
        # staged; force full restages at the next per-step launch
        self._break_decode_layout()
        if tr is not None:
            t = tr.now()
        chain = _sample_chain(samp)
        (packed,) = self._call_program(
            self._get_window_prog(),
            (toks, kvl, active, gen, budgets, eos_ids, base_keys, bt, samp), B)
        packed.copy_to_host_async()
        if tr is not None:
            tr.complete("engine.device_launch", t,
                        track=self._trace_track,
                        args={"step": sid, "bucket": B, "tokens": n,
                              "rows": n, "chunks": 0, "decode": n,
                              "logit_rows": n, "window": kp,
                              **self._launch_call,
                              "sample_chain": chain,
                              **self._ahead_args()})
        now = time.perf_counter()
        return _StepTicket(
            chunks=[], spec=[], batch=list(batch), sampled=None,
            packed=packed, logits=None, spec_slices=[], chunk_slots=[],
            batch_slots=list(range(n)), dispatch_s=now - t0,
            t_launch=now, launch_ns=tr.now() if tr is not None else 0,
            step=self.launches, inflight=self.overlap, window=kp,
            sample_chain=chain)

    def _apply_window(self, batch, batch_slots, sampled, ok, dur,
                      finished, window, sample_chain):
        """Drain one completed K-step window: ONE materialized [K, B]
        token (and finiteness) grid commits as up to K per-token steps
        per row, in iteration-major order — the exact per-token sequence
        (cache commit of the previous token, clock advance, append,
        penalty mask, stream callback, retire check) the per-step path
        runs, so prefix-cache content, retirement timing, and callbacks
        are indistinguishable from K=1.  The host replays the device's
        freeze logic: a row leaves the walk when it retires (eos/length
        — the same predicates the active-mask evaluated on device) or
        quarantines on a non-finite iteration; its later columns are the
        frozen filler values the loop carried and are never committed.
        ``window`` is the ticket's launched K' — a shrunk window's grid
        still arrives [decode_window, B] wide (the compiled driver's
        static K), so the drain MUST stop at K' or the budget-frozen
        rows would commit their repeated filler columns."""
        notify, retire, _, commit_token = self._row_calls()
        K = min(int(sampled.shape[0]), int(window))
        occ = len(self._running) / self.max_num_seqs
        alive = {req.rid for req in batch}
        committed = 0
        iters = 0
        for i in range(K):
            if not alive:
                break
            iters += 1
            for req, s in zip(batch, batch_slots):
                if req.rid not in alive:
                    continue
                if not ok[i, s]:
                    alive.discard(req.rid)
                    self._quarantine(req, finished)
                    continue
                if self.enable_prefix_caching:
                    commit_token(req.rid, req.generated[-1])
                req.cached += 1
                tok = int(sampled[i, s])
                req.generated.append(tok)
                if req.seen is not None:
                    req.seen[tok] = True
                committed += 1
                notify(req, (tok,))
                retire(req, finished)
                if not self._is_running(req):
                    alive.discard(req.rid)
        self.pad_stats["real"] += committed
        self.pad_stats["padded"] += iters * self.max_num_seqs
        self.pad_stats["legacy_padded"] += iters * self.max_num_seqs
        # one pass of the epilogue an iteration; temps ride the whole
        # window, so every pass takes the branch the launch's samp named
        self.sample_stats["launches"] += iters
        self.sample_stats["chain_launches"] += iters * sample_chain
        if committed:
            self.stats.record_decode(dur, committed, occ, rounds=iters)
        self.stats.set_decode_window(K)

    def _quarantine(self, req, finished: list) -> None:
        """Retire one sequence whose step logits came back non-finite.

        The sequence's pages leave through ``release`` (decref-only:
        pages shared with healthy neighbours survive, and the possibly-
        corrupt unshared tail is dropped WITHOUT registering in the
        prefix cache — corrupt K/V must never become a future cache
        hit).  Clients see finish_reason="numerical_error"; the rest of
        the batch is untouched."""
        self._give_back(req.rid, "release")
        self._leave_running(req)
        if self.drafter is not None:
            self.drafter.release(req.rid)
        out = RequestOutput(rid=req.rid, prompt=list(req.prompt),
                            generated=list(req.generated),
                            finish_reason="numerical_error")
        if self.retain_outputs:
            self._finished[req.rid] = out
        finished.append(out)
        self.stats.record_quarantine()
        self.stats.record_abort("numerical_error")
        if self.stats.windows is not None:
            self.stats.record_finish_quality(False)
            self.stats.record_request_latency(
                time.perf_counter() - req.t_arrival)
        fl = self.flight
        if fl is not None:
            fl.finished(req.rid, reason="numerical_error",
                        generated=len(req.generated),
                        tier=self._tier(), quarantined=True)
        tr = self.tracer
        if tr is not None:
            tr.instant("engine.quarantine", track=self._trace_track,
                       args={"rid": req.rid, "step": self._commit_step})
            tr.async_end("req", f"{self._trace_track}:{req.rid}",
                         args={"finish_reason": "numerical_error"})
        self._notify_finish(req, out)

    # ------------------------------------------------------------------
    # hierarchical KV tier (host-DRAM spill pool, inference/kv_tier.py)
    # ------------------------------------------------------------------

    def prefetch_hint(self, hashes) -> None:
        """Pre-stage a returning user's spilled pages: queue the prefix
        chain hashes of a prompt about to be submitted so the next
        step-boundary drain restores them before the prefill is packed.
        THREAD-SAFE (the tier's hint deque is locked) — the one engine
        entry point the frontend router may call off-thread.  No-op
        without a tier."""
        tier = self.kv_tier
        if tier is not None:
            tier.hint(hashes)

    def _drain_kv_tier(self, tr) -> None:
        """Step-boundary tier drain — the ONLY place spill/restore bytes
        cross the HBM/host boundary (graft-lint's host-copy-in-step-path
        keeps it out of the dispatch/complete hot phases).
        Spill: pages evict_parked quarantined copy out to the host pool
        and their HBM blocks free.  Restore: router prefetch hints, then
        the waiting queue's prompt chains, pull tier-resident pages back
        into free HBM blocks, re-registered content-addressed — from
        admission's point of view they are ordinary prefix-cache
        content.  Everything is eager array ops on materialized pools:
        ``compile_counts`` is untouched and restored bytes are the exact
        spilled bytes (the A/B byte-identity pin)."""
        tier = self.kv_tier
        int8 = self.kv_dtype == "int8"
        pending = self.blocks.take_spill_pending()
        if pending:
            blks = np.array([b for b, _ in pending], np.int32)
            kc = np.asarray(self._kc[:, blks])
            vc = np.asarray(self._vc[:, blks])
            if int8:
                ks = np.asarray(self._ks[:, blks])
                vs = np.asarray(self._vs[:, blks])
            stored = 0
            for i, (blk, hashes) in enumerate(pending):
                arrays = {"kc": kc[:, i], "vc": vc[:, i]}
                if int8:
                    arrays["ks"] = ks[:, i]
                    arrays["vs"] = vs[:, i]
                if tier.insert(hashes, arrays):
                    stored += 1
                # these hashes left HBM: a past restore no longer backs
                # a future admission hit
                self._staged_hashes.difference_update(hashes)
            self.stats.record_kv_spill(len(pending), stored)
            if tr is not None:
                tr.instant("kv_tier.spill", track=self._trace_track,
                           args={"step": self.launches + 1,
                                 "pages": len(pending), "stored": stored})

        restored = []                     # [(block, tier entry)]
        for h in self._tier_wanted_hashes(tier):
            if not self.blocks.num_free:
                break                     # opportunistic: never evict
            if self.blocks.has_hash(h):
                continue                  # covered earlier this drain
            entry = tier.take(h)
            if entry is None:
                continue
            blk = self.blocks.adopt_restored(entry["hashes"])
            if blk is None:               # unreachable given the guards
                tier.insert(entry["hashes"], entry["arrays"])
                break
            restored.append((blk, entry))
            self._staged_hashes.update(entry["hashes"])
        if restored:
            blks = np.array([b for b, _ in restored], np.int32)
            kc = np.stack([e["arrays"]["kc"] for _, e in restored], axis=1)
            vc = np.stack([e["arrays"]["vc"] for _, e in restored], axis=1)
            self._kc = self._kc.at[:, blks].set(kc)
            self._vc = self._vc.at[:, blks].set(vc)
            if int8:
                # scale rows travel with their pages; restored blocks are
                # NOT fresh (adopt_restored discarded them), so the
                # launch's fresh-mask reset cannot zero these rows
                ks = np.stack([e["arrays"]["ks"] for _, e in restored],
                              axis=1)
                vs = np.stack([e["arrays"]["vs"] for _, e in restored],
                              axis=1)
                self._ks = self._ks.at[:, blks].set(ks)
                self._vs = self._vs.at[:, blks].set(vs)
            if self.tp > 1:
                # keep the pools' mesh layout exactly as constructed so
                # the compiled step sees identically-sharded donations
                self._kc = jax.device_put(self._kc, self._kv_sharding)
                self._vc = jax.device_put(self._vc, self._kv_sharding)
                if int8:
                    self._ks = jax.device_put(self._ks, self._kv_sharding)
                    self._vs = jax.device_put(self._vs, self._kv_sharding)
            self.stats.record_kv_restore(len(restored))
            if tr is not None:
                tr.instant("kv_tier.restore", track=self._trace_track,
                           args={"step": self.launches + 1,
                                 "pages": len(restored)})
        self.stats.set_spill_tier(tier.stats())

    def _tier_wanted_hashes(self, tier) -> list:
        """Chain hashes worth restoring this drain, in chain order,
        deduped: router prefetch hints first (pre-staging a returning
        user), then the waiting queue's front prompts (admission's tier
        consult on a prefix-cache miss, one-shot per waiting episode).
        Each chain walks while its prefix stays servable — HBM-resident
        hashes skip, tier-resident ones restore, and the walk stops at
        the first hash neither holds (a contiguous prefix match can
        never reach later pages)."""
        chains = tier.drain_hints()
        n = 0
        for req in self._waiting:
            if n >= self.max_num_seqs:
                break
            n += 1
            if req.tier_checked == tier.gen:
                continue          # nothing new spilled since last consult
            req.tier_checked = tier.gen
            chains.append(prefix_chain_hashes(req.tokens, self.block_size))
        wanted: list = []
        seen: set = set()
        for chain in chains:
            for h in chain:
                if self.blocks.has_hash(h) or h in seen:
                    continue
                if tier.lookup(h):
                    seen.add(h)
                    wanted.append(h)
                else:
                    break
        return wanted

    # The running set has ONE membership test, ``_is_running``, and it
    # is O(1): a request holds a batch slot (``req.slot >= 0``) exactly
    # while it is in ``_running``.  The invariant is kept here and
    # nowhere else: ``_join_running`` is the one place ``_running`` is
    # appended to, ``_leave_running`` the one place it is removed from
    # (retirement, preemption, quarantine, abort), and nothing else
    # writes ``req.slot``.

    def _is_running(self, req) -> bool:
        return req.slot >= 0

    def _join_running(self, req) -> None:
        req.slot = self._slot_used.index(False)
        self._slot_used[req.slot] = True
        self._running.append(req)

    def _leave_running(self, req) -> None:
        run = self._running
        del run[next(i for i, r in enumerate(run) if r is req)]
        self._slot_used[req.slot] = False
        req.slot = -1

    def _admit(self) -> list:
        """Pull waiting requests into the running set while batch slots
        and pool pages allow.  With prefix caching, admission matches the
        prompt's token chain against the cache and allocates only the
        miss suffix; chunked prefill means admission is no longer gated
        on the per-step token budget."""
        if self.pressure is not None and self.pressure.admission_paused:
            return []
        admitted = []
        while self._waiting and len(self._running) < self.max_num_seqs:
            req = self._waiting[0]
            if self.enable_prefix_caching:
                hit = self.blocks.acquire(req.rid, req.tokens)
                if hit is None:
                    break
                req.cached = hit
                self.stats.record_cache_lookup(hit, len(req.tokens) - hit)
                if hit and self._staged_hashes:
                    # prefetch-hit attribution: hit pages whose chain
                    # hashes a tier restore staged (by hash, so block
                    # reuse cannot misattribute); each staged hash pays
                    # out at most once
                    used = [h for h in self.blocks.chain_hashes(req.rid)
                            if h in self._staged_hashes]
                    if used:
                        self._staged_hashes.difference_update(used)
                        self.stats.record_prefetch_hits(len(used))
            else:
                if not self.blocks.allocate(req.rid, len(req.tokens)):
                    break
                req.cached = 0
            self._waiting.popleft()
            req.arrival = self._arrival
            self._arrival += 1
            self._invalidate_bt(req.rid)
            self._join_running(req)
            admitted.append(req)
            # queue wait = arrival -> this admission (for a preempted
            # request that re-admits, arrival -> LATEST admission: the
            # whole stall was service latency)
            qw = time.perf_counter() - req.t_arrival
            self.stats.record_queue_wait(qw)
            fl = self.flight
            if fl is not None:
                fl.admitted(req.rid, queue_wait_s=qw,
                            cache_hit_tokens=req.cached,
                            tier=self._tier())
            tr = self.tracer
            if tr is not None:
                tr.instant("request.admitted", track=self._trace_track,
                           args={"rid": req.rid, "cached": req.cached,
                                 "step": self.launches + 1})
        return admitted

    def _schedule_prefill_chunks(self) -> list:
        """Pack at most max_prefill_tokens pending prompt tokens into this
        step, FCFS, resuming partially-prefilled requests first.  The
        budget rule itself is ``policy.pack_prefill_chunks`` (shared with
        the fleet simulator); the engine hangs copy-on-write resolution
        for each chunk's first write position (the only spot a chunk can
        touch a shared page) on its admit hook, so a CoW preemption skips
        the victim without consuming budget."""
        chunks: list = []

        def admit(req):
            if not self._is_running(req):
                return False
            if self.enable_prefix_caching:
                # may preempt req (False) or drop an earlier chunk's
                # owner from the accumulator (drop_from)
                return self._resolve_cow(req, self._pos(req),
                                         drop_from=chunks)
            return True

        ordered = sorted(list(self._running), key=lambda r: r.arrival)
        return pack_prefill_chunks(
            ((r, len(r.tokens) - self._pos(r)) for r in ordered),
            self.max_prefill_tokens, admit=admit, out=chunks)

    def _resolve_cow(self, req, pos: int, drop_from: list | None = None) \
            -> bool:
        """Privatize the page holding ``pos`` if it is shared, preempting
        victims while the pool has no page for the copy.  False when req
        itself had to be preempted.  (The copy is queued behind the
        launch in flight, which does not write the shared page.)"""
        while True:
            try:
                cw = self.blocks.cow_if_shared(req.rid, pos)
            except BlockPoolExhausted:
                if self._ahead_of is not None:
                    raise _AheadAbandoned("pool") from None
                victim = self._pick_victim(exclude=req)
                if victim is None:
                    self._preempt(req)
                    return False
                self._preempt(victim)
                if drop_from is not None:
                    self.sched_refilters += 1
                    drop_from[:] = [c for c in drop_from
                                    if c[0] is not victim]
                continue
            if cw is not None:
                self._apply_cow(*cw)
                self.stats.record_cow()
            return True

    def _reserve_decode_pages(self, batch: list) -> list:
        """Grow each sequence's table for the token this step will write
        (plus a private copy of a still-shared tail page); preempt the
        youngest runner whenever the pool comes up short (a dispatch
        ahead never preempts: it is abandoned)."""
        ok = []
        for req in sorted(batch, key=lambda r: r.arrival):
            if not self._is_running(req):  # evicted as a victim earlier
                continue
            while req is not None:
                if not self.blocks.ensure(req.rid, self._pos(req) + 1):
                    if self._ahead_of is not None:
                        raise _AheadAbandoned("pool")
                    victim = self._pick_victim(exclude=req)
                    if victim is None:
                        self._preempt(req)
                        req = None
                        break
                    self._preempt(victim)
                    self.sched_refilters += 1
                    ok = [r for r in ok if r is not victim]
                    continue
                if self.enable_prefix_caching:
                    pre0 = self.stats.preemptions
                    if not self._resolve_cow(req, self._pos(req)):
                        req = None
                        break
                    if self.stats.preemptions != pre0:
                        # the copy's victims may stand among the rows
                        # already reserved
                        self.sched_refilters += 1
                        ok = [r for r in ok if self._is_running(r)]
                break
            if req is not None:
                ok.append(req)
        return ok

    def _pick_victim(self, exclude):
        """Youngest-arrival running sequence other than ``exclude``."""
        cands = [r for r in self._running if r is not exclude]
        if not cands:
            return None
        return max(cands, key=lambda r: r.arrival)

    def _preempt(self, req) -> None:
        """Return req's pages and requeue it (front of the line) for
        recomputation: its next prefill covers prompt + tokens generated
        so far, which rebuilds the exact KV state — greedy decoding
        resumes token-identically.  With prefix caching the freed full
        pages park in the cache, so the recompute's admission hits the
        very pages this preemption returned and re-prefills only the
        tail."""
        self.blocks.free(req.rid)
        self._leave_running(req)
        req.tokens = list(req.prompt) + list(req.generated)
        req.cached = 0
        # its freed pages may spill while it waits: re-consult the tier
        req.tier_checked = -1
        self._invalidate_bt(req.rid)
        self._waiting.appendleft(req)
        if self.drafter is not None:
            self.drafter.release(req.rid)
        self.stats.record_preemption()
        fl = self.flight
        if fl is not None:
            fl.preempted(req.rid)
        if self.tracer is not None:
            self.tracer.instant("request.preempted",
                                track=self._trace_track,
                                args={"rid": req.rid,
                                      "step": self.launches + 1})

    def _give_back(self, rid: int, how: str) -> None:
        """Return a retired request's pages (``how``: ``BlockManager.
        free``, or ``.release`` for a row that must leave nothing in the
        prefix cache).  Where the launch queued behind the one being
        committed already holds the row (a stop token, or a non-finite
        row, that only this commit could see), that row is dropped
        unapplied when its launch completes and the pages go back then:
        until the last launch that names them has come back they are
        neither handed to another row nor registered in the prefix
        cache.  One device queue orders everything after."""
        q = self._queued
        if q is not None and rid in q.slot_of:
            q.dropped[rid] = how
        else:
            getattr(self.blocks, how)(rid)

    def _maybe_retire(self, req, finished: list) -> None:
        eos = req.eos_token_id
        if eos is not None and req.generated[-1] == int(eos):
            reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            reason = "length"
        else:
            return
        tr = self.tracer
        if tr is not None:
            t = tr.now()
        self._give_back(req.rid, "free")
        self._leave_running(req)
        out = RequestOutput(rid=req.rid, prompt=list(req.prompt),
                            generated=list(req.generated),
                            finish_reason=reason)
        if self.retain_outputs:
            self._finished[req.rid] = out
        finished.append(out)
        if self.drafter is not None:
            self.drafter.release(req.rid)
        self.stats.record_retirement()
        if self.stats.windows is not None:
            self.stats.record_finish_quality(True)
            self.stats.record_request_latency(
                time.perf_counter() - req.t_arrival)
        fl = self.flight
        if fl is not None:
            fl.finished(req.rid, reason=reason,
                        generated=len(req.generated),
                        tier=self._tier())
        if tr is not None:
            tr.complete("engine.retire", t, track=self._trace_track,
                        args={"step": self._commit_step, "rid": req.rid,
                              "finish_reason": reason})
            tr.async_end("req", f"{self._trace_track}:{req.rid}",
                         args={"finish_reason": reason,
                               "generated": len(req.generated)})
        self._notify_finish(req, out)

    # ------------------------------------------------------------------
    # speculative decoding: propose -> verify -> accept/rollback
    # ------------------------------------------------------------------

    def _split_spec(self, batch: list):
        """Ask the drafter for up to spec_k tokens per eligible sequence.
        Sequences with no proposal (or speculation off/disabled/cut to
        zero by length limits) fall through to plain decode."""
        if self.drafter is None:
            return [], batch
        spec, plain = [], []
        cap = self.max_spec_k
        if self.pressure is not None:
            # under pressure, shrinking drafts is the cheapest lever:
            # verify windows are the largest transient page consumers
            cap = self.pressure.spec_k_cap(self.max_spec_k)
        for req in batch:
            k = 0 if req.spec_disabled else min(req.spec_k, cap)
            # the verify step writes K/V at cached..cached+k, so the
            # sequence may hold at most max_model_len tokens afterwards;
            # drafting past max_new_tokens (plus the bonus token) is waste
            k = min(k,
                    self.max_model_len - len(req.prompt) - len(req.generated),
                    req.max_new_tokens - len(req.generated) - 1)
            if k <= 0:
                plain.append(req)
                continue
            context = list(req.prompt) + list(req.generated)
            drafts, qd = self.drafter.propose(req.rid, context, k)
            if not drafts:
                plain.append(req)
                continue
            spec.append((req, [int(t) for t in drafts[:k]], qd))
        return spec, plain

    def _page_starts(self, a: int, b: int) -> list:
        """First written position in each page the write window [a, b]
        (inclusive) touches — the positions _resolve_cow must privatize."""
        bs = self.block_size
        out = [a]
        p = (a // bs + 1) * bs
        while p <= b:
            out.append(p)
            p += bs
        return out

    def _reserve_verify_pages(self, spec: list):
        """Grow each speculative sequence's table for its K+1 writes and
        privatize every shared page in the window.  The pool is never
        preempted FOR speculation: when ensure() comes up short the draft
        shrinks (k -> k-1 -> ... -> plain decode) instead.  CoW of the
        first write position is required for plain decode too, so that
        path keeps the usual victim-preemption behaviour."""
        ok, demoted = [], []
        for req, drafts, qd in spec:
            if not self._is_running(req):
                continue
            k = len(drafts)
            while k > 0 and not self.blocks.ensure(req.rid,
                                                   req.cached + k + 1):
                k -= 1
            if k == 0:
                demoted.append(req)
                continue
            drafts = drafts[:k]
            if self.enable_prefix_caching:
                alive = True
                pre0 = self.stats.preemptions
                for pos in self._page_starts(req.cached, req.cached + k):
                    if not self._resolve_cow(req, pos):
                        alive = False           # req itself was preempted
                        break
                if self.stats.preemptions != pre0:
                    self.sched_refilters += 1
                    ok = [it for it in ok if self._is_running(it[0])]
                if not alive:
                    continue
            ok.append((req, drafts, qd))
        return ok, demoted

    def _apply_spec_result(self, req, drafts, qd, lg, finished,
                           calls) -> int:
        """Turn one sequence's verify logits into emitted tokens: run
        rejection-sampling acceptance, commit the accepted prefix's K/V,
        truncate the rejected tail out of the page table (scrubbing its
        content hashes), and advance the request exactly as that many
        plain decode steps would have.  Returns tokens emitted.
        ``calls``: ``_row_calls``."""
        from .spec_decode import verify_and_accept

        notify, retire, _, commit_token = calls

        k = len(drafts)
        rng = None
        if req.temperature > 0.0:
            # keyed by (seed, position): reproducible across scheduling
            # orders and preemptions, like _req_key on the device path
            rng = np.random.Generator(np.random.Philox(
                key=[req.seed & 0xFFFFFFFF, len(req.generated)]))
        n_acc, emitted = verify_and_accept(
            lg, drafts, q_dists=qd, temperature=req.temperature,
            top_k=req.top_k, top_p=req.top_p,
            penalty=req.repetition_penalty, seen=req.seen, rng=rng)
        # cut to the generation budget, and at the first eos token
        room = req.max_new_tokens - len(req.generated)
        emitted = emitted[:room]
        if req.eos_token_id is not None:
            eos = int(req.eos_token_id)
            if eos in emitted:
                emitted = emitted[:emitted.index(eos) + 1]
        m = len(emitted)                              # >= 1: room >= 1
        # K/V validity: positions cached..cached+n_acc hold
        # [generated[-1], accepted drafts]; m <= n_acc + 1 tokens advance
        # the clock, and when the m-th is the bonus/resample its K/V is
        # written by the NEXT step (decode invariant), not this one.
        if self.enable_prefix_caching:
            for tok in [req.generated[-1]] + emitted[:m - 1]:
                commit_token(req.rid, tok)
        req.cached += m
        # roll the speculative tail (rejected drafts + over-reserved
        # pages) back out of the table; prefix-cache hashes covering
        # rolled-back K/V are scrubbed inside truncate
        rolled = self.blocks.truncate(req.rid, req.cached)
        req.generated.extend(emitted)
        if req.seen is not None:
            req.seen[emitted] = True
        notify(req, emitted)
        j = m - 1 if m == n_acc + 1 else m            # emitted draft count
        if k:                                         # zero-draft rows are
            req.spec_proposed += k                    # plain decode riding
            req.spec_accepted += min(j, n_acc)        # the verify launch
            self.stats.record_spec(proposed=k, accepted=min(j, n_acc),
                                   emitted=m, rollback=k - j,
                                   pages_rolled=rolled)
            fl = self.flight
            if fl is not None:
                fl.spec_round(req.rid, min(j, n_acc), k - j)
            if (not req.spec_disabled
                    and req.spec_proposed >= self.spec_window
                    and req.spec_accepted
                    < self.spec_accept_floor * req.spec_proposed):
                req.spec_disabled = True
                self.stats.record_spec_disable()
            self.drafter.commit(
                req.rid, len(req.prompt) + len(req.generated) - (m - j))
        retire(req, finished)
        return m

    # ------------------------------------------------------------------
    # copy-on-write page copy (device side)
    # ------------------------------------------------------------------

    def _make_cow_fn(self):
        """(unjitted page-copy fn, intended donate_argnums) — the spec the
        analyzer sees; _apply_cow jits it (CPU drops donation: the CPU
        runtime cannot alias and would warn every call).  It copies page
        s to page d in every pool the engine has, whatever their shapes
        (each is [L, num_blocks, ...]): K and V; in int8 mode the page's
        scale-pool rows along with its data — the dst page is a live
        replica, so BlockManager excludes it from the fresh-page scale
        reset; for a latent model the one pool of cached rows."""
        n = len(self._pools())
        # the pools the block table's page ids index (a window layer's
        # pool has ids of its own; nothing shares its pages)
        paged = n - sum(x is not None for x in (self._kw, self._vw))

        def run(*args):
            s, d = args[n:]
            return tuple(x.at[:, d].set(x[:, s]) if i < paged else x
                         for i, x in enumerate(args[:n]))

        return run, tuple(range(n))

    def _apply_cow(self, src: int, dst: int) -> None:
        """Copy page src -> dst across every layer's K and V cache.  The
        copy is dispatched immediately so device program order keeps it
        ahead of any later prefill/decode write into dst."""
        tr = self.tracer
        if tr is not None:
            t = tr.now()
        if self._cow_prog is None:
            run, donate = self._make_cow_fn()
            if self._platform == "cpu":
                donate = ()
            self._cow_prog = jax.jit(_named(run, "kv_cow"),
                                     donate_argnums=donate)
            self._program_built("cow", "kv_cow")
        self._set_pools(self._cow_prog(*self._pools(), np.int32(src),
                                       np.int32(dst)))
        if tr is not None:
            # no span of its own: a nested one would leave
            # engine.schedule's self time; the schedule span reports the
            # sum (``cow``, ``cow_ns``)
            self._cow_n += 1
            self._cow_ns += tr.now() - t

    # ------------------------------------------------------------------
    # the compiled ragged step
    # ------------------------------------------------------------------

    def _record_program(self, name: str) -> None:
        """Note the paths a step program was just built with.  The
        builders read ``attention_path`` / ``matmul_path`` at build, so
        the record is what the program runs."""
        self.program_paths[name] = {"attention": self.attention_path,
                                    "matmul": self.matmul_path}

    def _program_built(self, kind: str, name: str) -> None:
        """Count one jitted program (``compile_counts[kind]``) and say so
        in the trace under the name the jit was given."""
        self.compile_counts[kind] = self.compile_counts.get(kind, 0) + 1
        tr = self.tracer
        if tr is not None:
            tr.instant("engine.program_built", track=self._trace_track,
                       args={"name": name, "step": self.launches + 1})

    def _ragged_bucket(self, n_tokens: int) -> int:
        """Flat-token bucket for a launch: pure-decode-sized launches pad
        to max_num_seqs; with a drafter, speculation-sized launches (every
        running row carrying a full draft) stop at the static logit-row
        width max_num_seqs * (max_spec_k + 1) when that sits below the
        prefill bucket — otherwise a verify round of B*(k+1) rows would
        pad all the way up to prefill_token_bucket every step; anything
        larger rounds up to a multiple of prefill_token_bucket.  The
        tiers bound the program count at 2 + (max launch size) / bucket."""
        if n_tokens <= self.max_num_seqs:
            return self.max_num_seqs
        if self._with_logits and \
                n_tokens <= self._Lq < self.prefill_token_bucket:
            return self._Lq
        tb = self.prefill_token_bucket
        return -(-n_tokens // tb) * tb

    def _get_ragged_prog(self, Tq: int):
        prog = self._ragged_progs.get(Tq)
        if prog is None:
            run, donate = self._make_ragged_fn(Tq)
            if self._platform == "cpu":
                donate = ()
            name = f"ragged_step_t{Tq}"
            prog = jax.jit(_named(run, name), donate_argnums=donate)
            self._ragged_progs[Tq] = prog
            self._program_built("ragged", name)
            self._record_program(f"ragged:{Tq}")
        return prog

    def _get_tail_prog(self):
        """The sampled rows' chain as a program of its own (one an
        engine, whatever the token bucket; built at the first launch
        that holds a sampled row): from the logits a step program
        handed on and the launch's ``samp``, every row's token as
        ``sample_tokens`` gives it (a greedy row's is the step
        program's own), once as it is and once in the packed vector's
        first rows, whose other rows (finiteness, expert counts) pass
        through."""
        if self._tail_prog is None:
            self._tail_prog = jax.jit(_named(_sampled_tail, "sampled_tail"))
            self._program_built("sampled_tail", "sampled_tail")
        return self._tail_prog

    def _step_shared(self, Tq: int) -> dict:
        """What the layers of a step program over ``Tq`` flat tokens
        share whatever the launch holds: ``layer_stack.step_context``
        minus the row layout, resolved once per program build.  Under
        tp the body runs on PER-SHARD shapes: a contiguous block of
        nh/tp query heads attending over kvh/tp KV heads (GQA groups
        never straddle shards — tp divides kvh)."""
        cfg = self.config
        mm, embed, head_logits = self._weight_ops()
        shared = dict(
            Tq=Tq, bs=self.block_size, tp=self.tp, mm=mm, embed=embed,
            head_logits=head_logits, shard_head=self._shard_head,
            kinds=self._layer_kinds, scanned=self._scanned,
            eps=cfg.rms_norm_eps,
            use_pallas=self.attention_path.startswith("pallas"))
        if self._latent:
            shared.update(cfg=cfg, attn=self._attn, slots=self._slots)
        else:
            shared.update(attn=self._attn, kvh=self._kvh // self.tp,
                          d=self._hd)
        if self._windowed:
            shared.update(cfg=cfg, window=self._window,
                          pool_index=self._pool_index)
        if hasattr(cfg, "step_fields"):
            # what the MODEL says of its stack, its norm and what its
            # layers hand one another inside a step
            shared.update(cfg.step_fields(self._act_dtype))
        return shared

    def _make_ragged_fn(self, Tq: int):
        """The one serving step program: Tq flat query tokens from up to
        max_num_seqs ragged rows.  A prefill chunk, a resumed chunk, a
        decode token, and a k-draft verify window are all rows of the
        same launch, differing only in query length — each layer writes
        the packed tokens' K/V (or latent rows) into the paged cache at
        their absolute positions (over int8 pages it QUANTIZES them at
        commit time and attention dequantizes at read time), then ragged
        paged attention lets every token attend to its own row's pages
        causally.  Sampled tokens come back for the logit rows in
        ``lidx``, once as they are (the next launch's ``prev``) and
        once more in the ONE vector the host reads, with the finiteness
        flags and what a model's expert layers counted behind them
        (``_pack_results``); with a drafter the raw [Lq, V] logits ride
        along for host-side draft acceptance.  A token the host does not
        have yet (the sample of the launch in front, when this one is
        dispatched ahead of its commit) is taken on the device: ``src``
        names its logit row in ``prev``, that launch's sampled tokens.

        The forward is ``layer_stack.forward`` for every model and page
        type (inference/layer_stack.py): the dense decoder is one
        scanned segment over its stacked weights, a latent-attention
        model runs layer after layer over its own arrays.  Either way
        the donated pools hold all layers, a layer writes its rows
        into them in place at (layer, page, slot) and its kernel reads
        them at a layer index: no layer-sized slice of a pool is made,
        and the pools that come back are the buffers that went in."""
        apart = self._tail_apart
        with_logits = self._with_logits or apart
        n_pools = len(self._pools())
        q8 = self.kv_dtype == "int8"
        windowed = self._windowed
        stateful = self._stateful
        # (``run`` below must not close over ``self``: a compiled program
        # that holds its engine keeps it alive past its last user)
        shared = self._step_shared(Tq)

        def run(params, *rest):
            # rest: the page pools (over int8 pages K, V and their
            # [L, num_blocks, H_kv] f32 scale pools, then fresh
            # [num_blocks] bool: pages whose scales reset this launch;
            # with a state instead slots [B] i32, each row's batch slot),
            # then toks [Tq] i32, rows packed back-to-back (tail padding
            # maps to the sentinel row); cu [B+1] i32 row offsets; kvl
            # [B] i32 valid KV per row AFTER this launch's writes; bt
            # [B+1, nblk] i32 (row B: the null row pads resolve to);
            # lidx [Lq] i32 flat index of each logit row; samp the
            # make_samp pytree, one row per logit row; prev [Lq] i32 the
            # ``sampled`` of the launch in front (not donated; zeros
            # when none is in flight); src [Tq] i32, for each flat
            # token -1 (``toks`` holds it) or the row of prev that does.
            # Under tp>1 this traces per shard: the pools and the q/k/v
            # projections arrive head-sliced, fresh..src arrive
            # replicated.
            # the jax.named_scope names here and in layer_stack are what
            # a device trace is read by (docs/observability.md)
            pools, host = rest[:n_pools], rest[n_pools:]
            toks, cu, kvl, bt, lidx, samp, prev, src = host[-8:]
            with jax.named_scope("prev_tokens"):
                toks = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], toks)
            seg, rel = _pa.ragged_segments(cu, kvl, Tq)
            # with window layers bt is both tables, [2, B+1, nblk]: the
            # global layers', then the window layers' (entries below a
            # row's window name the null page)
            tables = dict(bt=bt[0], btw=bt[1]) if windowed else dict(bt=bt)
            if stateful:
                # a row's state: its batch slot, or for a row of no
                # tokens the slot nobody holds; it starts from zeros
                # where the row's first token is its sequence's first
                n_q = cu[1:] - cu[:-1]
                tables.update(
                    state_slot=jnp.where(n_q > 0, host[0], kvl.shape[0]),
                    state_start=kvl == n_q)
            c = _ls.step_context(seg=seg, rel=rel, cu=cu, kvl=kvl,
                                 fresh=host[0] if q8 else None, **tables,
                                 **shared)
            logits, pools, counts = _ls.forward(params, toks, pools, c,
                                                lidx)
            with jax.named_scope("sample"):
                # (apart: the sampled rows' chain is a program of its
                # own, which takes the logits returned below)
                sampled = greedy_tokens(logits, samp)[1] if apart \
                    else sample_tokens(logits, samp)
                # per-row finiteness flag: the quarantine guard retires a
                # poisoned row host-side without touching its batchmates
                # (padded rows may be legitimately non-finite; the host
                # only consults live slots)
                fin = jnp.all(jnp.isfinite(logits), axis=-1)  # [Lq]
                # sampled stays on the device for the next launch
                # (``prev``); the host reads the packed vector alone
                out = (sampled, _pack_results(sampled, fin, counts))
            if with_logits:
                out += (logits,)
            return out + tuple(pools)

        # donation reuses the pool buffers (pages and scales) in place;
        # fresh is input-only.  _get_ragged_prog drops donation on CPU
        # (that runtime cannot alias and warns per call)
        return self._wrap_tp(run, 8 + q8 + stateful), \
            tuple(range(1, 1 + n_pools))

    def _consume_fresh(self):
        """Accumulate BlockManager's freshly handed-out pages into the
        persistent mask, hand a snapshot to the launch, and clear — the
        launch's in-program scale reset consumes the batch."""
        for b in self.blocks.drain_fresh():
            self._fresh_np[b] = True
        out = self._fresh_np.copy()
        self._fresh_np[:] = False
        return out

    def _call_program(self, prog, host_args, bucket: int):
        """The jitted call of a step launch: the parameters, the pools
        (over int8 pages the fresh-page mask after them) and the
        launch's ``host_args``; the pools that come back are kept and
        the outputs before them returned.  It counts the launch (the
        step id; ahead or, by reason, not), times the call alone and
        sums the bytes of the host arrays it is handed (``summary()``
        ``launch_call_time_s``, ``launch_arg_bytes``; ``call_ns`` and
        ``arg_bytes`` on ``engine.device_launch``: nothing is staged or
        placed differently for the reading, so the transfer and the
        dispatch stay one number) and, with a tracer installed,
        brackets the call in one
        ``engine.launch`` annotation carrying that id, so the profiler's
        own trace holds a host event a step that joins a device
        program's execution to the Tracer's ``engine.device_launch``.
        One call site: the program is the same whoever is watching."""
        pools = self._pools()
        args = (self.params,) + pools
        if self.kv_dtype == "int8":
            args += (self._consume_fresh(),)
        args += tuple(host_args)
        arg_bytes = _host_nbytes(args[1 + len(pools):])
        self.launches += 1
        if self._ahead_of is not None:
            self.launches_ahead += 1
        else:
            self.ahead_fallbacks[self._not_ahead] = \
                self.ahead_fallbacks.get(self._not_ahead, 0) + 1
        note = _NO_ANNOTATION if self.tracer is None else \
            jax.profiler.TraceAnnotation("engine.launch",
                                         step=self.launches,
                                         bucket=int(bucket))
        with note:
            t0 = time.perf_counter_ns()
            out = prog(*args)
            call_ns = time.perf_counter_ns() - t0
        self.stats.record_launch_call(call_ns, arg_bytes)
        if self.tracer is not None:
            # what engine.device_launch says of the call inside it
            self._launch_call = {"call_ns": call_ns,
                                 "arg_bytes": arg_bytes}
        self._set_pools(out[-len(pools):])
        return out[:-len(pools)]

    def _ahead_args(self) -> dict:
        """What ``engine.device_launch`` says of the pipeline: whether
        this launch was dispatched before the one in front of it was
        committed, and the reason where not."""
        if self._ahead_of is not None:
            return {"ahead": True}
        return {"ahead": False, "reason": self._not_ahead}

    def _packed(self, tr, t_pack: int, t_rows: int, **what) -> None:
        """Ends the ``engine.schedule`` span of the launch being
        prepared where its host inputs stand packed: choosing the rows
        and laying them out is one span, with what was packed (``rows``,
        ``tokens``, ``bucket``) and how long the flat tokens
        (``pack_ns``, from ``t_pack``) and the table rows (``table_ns``,
        from ``t_rows``) took of it."""
        if self._sched_open is None:      # a tracer installed mid-turn
            return
        t, args = self._sched_open
        self._sched_open = None
        tr.complete("engine.schedule", t, track=self._trace_track,
                    args={**args, **what, "pack_ns": t_rows - t_pack,
                          "table_ns": tr.now() - t_rows})

    def _launch_ragged(self, Tq, toks, cu, kvl, bt, lidx, samp,
                       real_tokens, src=None, slots=None):
        """One launch of the step program at bucket ``Tq``.  ``src``:
        the rows of the in-flight launch's ``sampled`` that ``toks``
        takes on the device (None: every token is staged in ``toks``).
        ``slots``: each row's batch slot, which a model with a state a
        sequence takes and no other.
        Returns the launch's ``sampled`` (for the next launch), the
        packed vector the host reads, already on its way, and the verify
        logits or None: unmaterialized device arrays."""
        ahead = self._ahead_of
        prev = self._no_prev if ahead is None else ahead.sampled
        if src is None:
            src = np.full((Tq,), -1, np.int32)
        self.pad_stats["real"] += int(real_tokens)
        self.pad_stats["padded"] += int(Tq)
        # counted once a launch; ``engine.device_launch`` carries the same
        pages = self._launch_pages = self._launch_kv_args(cu, kvl)
        self.pad_stats["kv_pages"] += pages["kv_pages"]
        for name in ("kv_pages_window", "kv_write_pages", "kv_write_tokens",
                     "index_keys_visible", "index_keys_selected",
                     "state_starts", "state_rows"):
            self.pad_stats[name] += pages.get(name, 0)
        self.sample_stats["launches"] += 1
        self.sample_stats["chain_launches"] += _sample_chain(samp)
        host = (toks, cu, kvl, bt, lidx, samp, prev, src)
        if self._stateful:
            host = (slots,) + host
        sampled, packed, *logits = self._call_program(
            self._get_ragged_prog(Tq), host, Tq)
        if self._tail_apart:
            if _sample_chain(samp):
                sampled, packed = self._get_tail_prog()(
                    logits[0], samp, packed)
            if not self._with_logits:
                logits = ()
        # the transfer starts when the execution ends, with nobody
        # asking: the host finds the vector there when it comes to read
        packed.copy_to_host_async()
        return sampled, packed, (logits[0] if logits else None)

    def _kv_pages(self, kvl) -> int:
        """Pages a launch's rows hold keys in: what the attention kernel
        walks, of the bucket * nblk page slots of the table."""
        return int((-(-np.asarray(kvl) // self.block_size)).sum())

    def _kv_write(self, cu, kvl) -> dict:
        """Pages a launch's new tokens touch (each row's, from the page
        of its first new token to the page of its last) and those
        tokens: what a layer's ``kv_write`` moves and what it writes."""
        kvl = np.asarray(kvl)
        n_q = np.diff(np.asarray(cu))[:len(kvl)]
        bs = self.block_size
        touched = np.where(n_q > 0,
                           (kvl - 1) // bs - (kvl - n_q) // bs + 1, 0)
        return {"kv_write_pages": int(touched.sum()),
                "kv_write_tokens": int(n_q.sum())}

    def _kv_pages_window(self, cu, kvl) -> int:
        """Pages the same rows hold in a WINDOW layer: from the page of
        the lowest key a row's first query sees to the page of its last
        key (``_kv_pages``: what one table for all layers would hold)."""
        kvl = np.asarray(kvl)
        first = kvl - np.diff(np.asarray(cu))[:len(kvl)]
        lo = np.maximum(first - self._window + 1, 0) // self.block_size
        return int(np.where(kvl > 0, -(-kvl // self.block_size) - lo,
                            0).sum())

    def _launch_kv_args(self, cu, kvl) -> dict:
        """The page counts of a launch, as ``engine.device_launch``
        carries them."""
        pages = self._kv_pages(kvl)
        out = {"kv_pages": pages, **self._kv_write(cu, kvl)}
        if self._windowed:
            out.update(kv_pages_uniform=pages,
                       kv_pages_window=self._kv_pages_window(cu, kvl))
        if self._index_topk:
            out.update(self._index_keys(cu, kvl))
        if self._stateful:
            n_q = np.diff(np.asarray(cu))[:len(kvl)]
            out.update(
                state_starts=int(((np.asarray(kvl) == n_q)
                                  & (n_q > 0)).sum()),
                state_rows=int(n_q.sum()))
        return out

    def _index_keys(self, cu, kvl) -> dict:
        """What an indexed layer's queries of this launch see and what
        they keep: a query at position p sees p + 1 keys and attends to
        the ``min(p + 1, index_topk)`` it selects, summed over the
        launch's rows (one layer's: every indexed layer has the same)."""
        kvl = np.asarray(kvl, np.int64)
        n_q = np.diff(np.asarray(cu, np.int64))[:len(kvl)]
        k = self._index_topk
        # positions first .. kvl - 1; those below k - 1 keep all they see
        first = kvl - n_q
        visible = n_q * kvl - n_q * (n_q - 1) // 2
        full = np.clip(kvl - np.maximum(first, k - 1), 0, None)  # keep k
        part = n_q - full                         # positions first ..
        selected = full * k + part * first + part * (part + 1) // 2
        return {"index_keys_visible": int(visible.sum()),
                "index_keys_selected": int(selected.sum())}

    def _advance_window(self, req, start: int, end: int) -> None:
        """Before a launch that holds req's queries at positions start
        .. end - 1: the window layers give back what lies below the
        window and take what the launch writes (a table-version bump
        where that changed anything; nothing without window layers)."""
        if self._windowed:
            self.blocks.window_advance(req.rid, start, end)

    def _table_row(self, req):
        """req's block-table row(s) as a launch takes them: its page
        list padded to the table's width; with window layers that list
        and, under it, the window layers'."""
        row = self.blocks.padded_table(req.rid, self.nblk)
        if not self._windowed:
            return row
        return np.stack([row, self.blocks.window_table(req.rid, self.nblk)])

    def _get_window_prog(self):
        """The compiled K-step decode window driver (one per engine —
        its shapes are fixed at [B] rows / K iterations, so unlike the
        ragged step it never re-specializes).  Compiling it adds exactly
        one new ``compile_counts`` key, ``"scan"``, and only for engines
        actually running decode_window > 1."""
        if self._window_prog is None:
            run, donate = self._make_window_fn()
            if self._platform == "cpu":
                donate = ()
            name = f"decode_window_k{self.decode_window}"
            self._window_prog = jax.jit(_named(run, name),
                                        donate_argnums=donate)
            self._program_built("scan", name)
            self._record_program(f"window:{self.decode_window}")
        return self._window_prog

    def _make_window_fn(self):
        """The device-resident K-step decode window program.

        One launch runs up to K = ``decode_window`` full decode steps
        without a host round-trip: a ``lax.while_loop`` whose body is
        EXACTLY the per-step decode program at Tq = B (the same
        ``layer_stack.forward``, the same LogitProcessor chain) plus the
        carry bookkeeping the host does between per-step launches —
        advance kv_lens, re-derive sampler keys as fold_in(base,
        generated), update the repetition-penalty ``seen`` mask, and
        freeze rows whose sampled token hits eos or whose generation
        budget fills (the same predicates ``_maybe_retire`` applies
        host-side).  Frozen rows redirect to the sentinel block-table
        row via ``decode_window_segments`` so their writes land in the
        null page like ragged padding; the loop exits early once every
        row froze.  The host drains the [K, B] token grid afterwards (it
        comes back with the finiteness grid in one vector, as a step's
        results do: ``_pack_results``) —
        logits and tokens never leave the device mid-window, which is
        the whole point.

        Over int8 pages the fresh-page scale reset HOISTS out of the
        loop.  The per-step program zeroes fresh pages' scale rows
        inside every layer because each launch consumes one fresh
        batch; here the whole window's pages are handed out before
        launch, and an in-body reset would wipe scales grown by earlier
        window iterations — so the reset runs ONCE, before iteration 0,
        when every fresh page is still unwritten (byte-equivalent)."""
        B = self.max_num_seqs
        K = self.decode_window
        n_pools = len(self._pools())
        q8 = self.kv_dtype == "int8"
        shared = self._step_shared(B)

        def run(params, *rest):
            # rest: the page pools (over int8 pages: and fresh, as the
            # ragged step), then toks [B] i32 last committed token per
            # row; kvl [B] i32 valid KV AFTER iteration 0's write;
            # active [B] bool; gen [B] i32 tokens generated so far (the
            # sampler-key counter); budgets [B] i32 max_new_tokens;
            # eos_ids [B] i32 (-1: no eos); base_keys [B,2] u32
            # PRNGKey(seed) per row; bt [B+1, nblk]; samp the make_samp
            # pytree (its "keys" field is dead — the body derives keys
            # from base_keys).
            pools, host = rest[:n_pools], rest[n_pools:]
            (toks, kvl, active, gen, budgets, eos_ids, base_keys, bt,
             samp) = host[-9:]
            rows = jnp.arange(B, dtype=jnp.int32)
            if q8:
                pools = pools[:2] + tuple(
                    jnp.where(host[0][None, :, None], 0.0, scales)
                    for scales in pools[2:])

            def step(carry):
                i, tok, kvl, active, gen, seen, pools, touts, fouts = carry
                seg, rel = _pa.decode_window_segments(active, kvl)
                cu_w, kvl_w = _pa.decode_window_rows(active, kvl)
                c = _ls.step_context(seg=seg, rel=rel, bt=bt, cu=cu_w,
                                     kvl=kvl_w, fresh=None, **shared)
                # every row is its own logit row (lidx == identity)
                logits, pools, _ = _ls.forward(params, tok, pools, c)
                with jax.named_scope("sample"):
                    keys = advance_keys(base_keys, gen)
                    sampled = sample_tokens(
                        logits, dict(samp, seen=seen, keys=keys))
                    fin = jnp.all(jnp.isfinite(logits), axis=-1)  # [B]
                # frozen rows carry their last committed token so the
                # grid's dead columns hold committed values, never
                # null-page garbage
                sampled = jnp.where(active, sampled, tok)
                touts = touts.at[i].set(sampled)
                fouts = fouts.at[i].set(fin | ~active)
                seen = seen.at[rows, sampled].set(
                    seen[rows, sampled] | active)
                nxt = active & (sampled != eos_ids) \
                    & (gen + 1 < budgets)
                adv = active.astype(jnp.int32)
                return (i + 1, sampled, kvl + adv, nxt, gen + adv,
                        seen, pools, touts, fouts)

            def cond(carry):
                return (carry[0] < K) & jnp.any(carry[3])

            carry = (jnp.int32(0), toks, kvl, active, gen,
                     samp["seen"], pools,
                     jnp.zeros((K, B), jnp.int32),
                     jnp.ones((K, B), jnp.bool_))
            carry = lax.while_loop(cond, step, carry)
            return (_pack_results(*carry[7:]),) + tuple(carry[6])

        # the one non-pool output (the [K, B] token and finiteness grids,
        # packed) is replicated after the in-body all-gathers — every
        # shard's while_loop sees identical replicated logits, so the
        # active-mask and the early-exit condition agree across shards
        # by construction
        return self._wrap_tp(run, 9 + q8, 1), tuple(range(1, 1 + n_pools))

    def _fill_samp(self, samp, s, req):
        samp["temps"][s] = req.temperature
        samp["top_k"][s] = req.top_k
        samp["top_p"][s] = req.top_p
        samp["penalty"][s] = req.repetition_penalty
        if req.seen is not None:
            np.copyto(samp["seen"][s], req.seen)
        if req.temperature > 0.0:
            # greedy rows never touch their key: an all-greedy launch
            # skips per-step key derivation entirely
            samp["keys"][s] = self._req_key(req)

    def _token_src(self, req) -> int:
        """Where the token req feeds into this launch is: -1 for the
        host (``req.generated[-1]``), or, when the launch in flight
        samples it, that launch's logit row for req."""
        return self._ahead_of.slot_of[req.rid] if req.inflight else -1

    def _run_ragged(self, chunks: list, spec: list, batch: list):
        """Pack this step's whole mix as ONE ragged launch.

        Row order: prefill chunks (scheduler order), speculative
        [last_token, drafts...] windows, plain decode tokens (slot
        order).  Returns (sampled tokens, the packed vector the host
        reads, per-spec-row logits or None, spec row slices, chunk logit
        slots, decode logit slots) — the first three are UNMATERIALIZED
        device arrays: the next launch takes the first, the caller's
        completion ticket blocks on the other two later."""
        total = sum(n for _, n in chunks) \
            + sum(len(d) + 1 for _, d, _ in spec) + len(batch)
        Tq = self._ragged_bucket(total)

        # decode fast path: steady pure-decode steps reuse the
        # persistent host buffers instead of repacking from scratch
        if not chunks and not spec:
            return self._run_ragged_decode(batch, Tq)

        # a decode row's token is the last one generated: on the host,
        # or (dispatch ahead) still on the device, where ``src`` finds it
        rows = [(req, req.tokens[self._pos(req):self._pos(req) + n], "c")
                for req, n in chunks]
        rows += [(req, [req.generated[-1]] + list(d), "s")
                 for req, d, _ in spec]
        rows += [(req, [0 if req.inflight else req.generated[-1]], "d")
                 for req in batch]

        B = self.max_num_seqs
        toks = np.zeros((Tq,), np.int32)
        src = np.full((Tq,), -1, np.int32)
        cu = np.zeros((B + 1,), np.int32)
        kvl = np.zeros((B,), np.int32)
        slots = np.full((B,), B, np.int32)
        bt = np.full(self._bt_shape, NULL_BLOCK, np.int32)
        lidx = np.zeros((self._Lq,), np.int32)
        samp = make_samp(self._Lq, self.config.vocab_size)
        spec_slices, chunk_slots, batch_slots = [], [], []

        tr = self.tracer
        if tr is not None:
            sid = self.launches + 1
            t = tr.now()
        off = 0      # flat-token cursor
        ls = 0       # logit-row cursor
        for i, (req, window, kind) in enumerate(rows):
            n = len(window)
            toks[off:off + n] = window
            cu[i + 1] = off + n
            kvl[i] = self._pos(req) + n
            slots[i] = req.slot
            if kind == "d" and req.inflight:
                src[off] = self._token_src(req)
            if kind == "s":
                # every window position is scored; acceptance is
                # sequential on host, so the device-sampled rows for
                # these slots go unused (samp defaults)
                lidx[ls:ls + n] = np.arange(off, off + n)
                spec_slices.append((ls, n))
                ls += n
            else:
                lidx[ls] = off + n - 1
                self._fill_samp(samp, ls, req)
                (chunk_slots if kind == "c" else batch_slots).append(ls)
                ls += 1
            off += n
        cu[len(rows) + 1:] = off
        if tr is not None:
            t_bt = tr.now()
        for i, (req, w, _k) in enumerate(rows):
            # (what the window gives back the launch in flight has read
            # by the time a later launch writes it: one device queue)
            self._advance_window(req, self._pos(req),
                                 self._pos(req) + len(w))
            bt[..., i, :] = self._table_row(req)
        if tr is not None:
            self._packed(tr, t, t_bt, rows=len(rows), tokens=total,
                         bucket=int(Tq))

        # padding a four-program step would have cost: a token-bucketed
        # chunk launch, plus the full-width verify launch when anything
        # speculates (folding decode rows), else the decode bucket
        tb = self.prefill_token_bucket
        ct = sum(n for _, n in chunks)
        legacy = max(tb, -(-ct // tb) * tb) if ct else 0
        if spec:
            legacy += B * (self.max_spec_k + 1)
        elif batch:
            legacy += B
        self.pad_stats["legacy_padded"] += legacy

        # the launch (re)packed every row's table fresh, and post-verify
        # truncate changes tables again — break the decode fast path's
        # layout reuse and force full restages next step
        self._break_decode_layout()

        if tr is not None:
            t = tr.now()
        sampled, packed, logits = self._launch_ragged(
            Tq, toks, cu, kvl, bt, lidx, samp, total, src, slots)
        if spec:
            # the verify logits are read too: on their way as well
            logits.copy_to_host_async()
        else:
            logits = None
        if tr is not None:
            # logit rows whose result is used: a chunk that ends its
            # prompt, every verify position, every decode row
            logit_rows = sum(1 for r, n in chunks
                             if self._pos(r) + n == len(r.tokens)) \
                + sum(n for _, n in spec_slices) + len(batch)
            tr.complete("engine.device_launch", t,
                        track=self._trace_track,
                        args={"step": sid, "bucket": int(Tq),
                              "tokens": total, "rows": len(rows),
                              "chunks": len(chunks), "decode": len(batch),
                              "logit_rows": logit_rows,
                              **self._launch_pages,
                              **self._launch_call,
                              "sample_chain": _sample_chain(samp),
                              **self._ahead_args()})
        # NO materialization here: sampled/packed/logits return as async
        # device arrays; _complete blocks on them (the dispatch path
        # must never force a host sync on step-program outputs)
        return sampled, packed, logits, spec_slices, chunk_slots, \
            batch_slots

    def _run_ragged_decode(self, batch: list, Tq: int):
        """Pure-decode launch over the persistent host buffers.  Rows
        repack incrementally ONLY while the layout signature — the rid
        order of the packed rows — is unchanged since the last pure-
        decode step through THIS buffer; retirement, admission,
        preemption, or any mixed launch in between changes the
        signature and forces a full repack, so ragged packing never
        reuses a stale row order.  Within a stable layout, block-table
        rows still refresh whenever the sequence's table version bumped
        (page growth/CoW).

        With overlap on, launches ALTERNATE between the two buffer sets
        (the previous launch may still be in flight and CPU PJRT can
        alias its input arrays)."""
        n = len(batch)
        bi = (1 - self._d_cur) if self.overlap else 0
        buf = self._dbufs[bi]
        samp = buf.samp
        layout = tuple(r.rid for r in batch)
        if layout != buf.layout:
            buf.layout = layout
            buf.bt[:] = NULL_BLOCK
            buf.kvl[:] = 0
            buf.slot[:] = self.max_num_seqs
            buf.slot[:n] = [req.slot for req in batch]
            buf.cu[:n + 1] = np.arange(n + 1)
            buf.cu[n + 1:] = n
            samp["temps"][:] = 0.0
            samp["top_k"][:] = 0
            samp["top_p"][:] = 1.0
            samp["penalty"][:] = 1.0
            samp["seen"][:] = False
            for s, req in enumerate(batch):
                samp["temps"][s] = req.temperature
                samp["top_k"][s] = req.top_k
                samp["top_p"][s] = req.top_p
                samp["penalty"][s] = req.repetition_penalty
            buf.bt_ver.clear()               # force table repacks below
        tr = self.tracer
        if tr is not None:
            sid = self.launches + 1
            t = tr.now()
        buf.src[:] = -1
        for s, req in enumerate(batch):
            if req.inflight:
                # the launch in flight samples it: taken on the device
                buf.toks[s] = 0
                buf.src[s] = self._token_src(req)
            else:
                buf.toks[s] = req.generated[-1]
            buf.kvl[s] = self._pos(req) + 1
            if req.seen is not None:
                np.copyto(samp["seen"][s], req.seen)
            if req.temperature > 0.0:
                samp["keys"][s] = self._req_key(req)
        if tr is not None:
            t_bt = tr.now()
        for s, req in enumerate(batch):
            self._advance_window(req, int(buf.kvl[s]) - 1, int(buf.kvl[s]))
            ver = self.blocks.table_version(req.rid)
            if buf.bt_ver.get(req.rid) != ver:
                buf.bt[..., s, :] = self._table_row(req)
                buf.bt_ver[req.rid] = ver
        if tr is not None:
            self._packed(tr, t, t_bt, rows=n, tokens=n, bucket=int(Tq),
                         fast_path=True)
        self.pad_stats["legacy_padded"] += self.max_num_seqs
        if tr is not None:
            t = tr.now()
        sampled, packed, _ = self._launch_ragged(Tq, buf.toks, buf.cu,
                                                 buf.kvl, buf.bt,
                                                 self._d_lidx, samp, n,
                                                 buf.src, buf.slot)
        if tr is not None:
            tr.complete("engine.device_launch", t,
                        track=self._trace_track,
                        args={"step": sid, "bucket": int(Tq), "tokens": n,
                              "rows": n, "chunks": 0, "decode": n,
                              "logit_rows": n,
                              **self._launch_pages,
                              **self._launch_call,
                              "sample_chain": _sample_chain(samp),
                              **self._ahead_args()})
        self._d_cur = bi
        return sampled, packed, None, [], [], list(range(n))

    def _inject_nan(self, ok, live_slots: list):
        """FaultPlan NaN seam: corrupt one LIVE logit row's finiteness
        flag, as if the device had produced a non-finite row there.
        Flipping the host-side flag (rather than the device logits)
        keeps the injection exact and free when no plan is set; the
        quarantine path downstream is the same either way."""
        plan = self.fault_plan
        if plan is None or not live_slots:
            return ok
        j = plan.take_nan_row(len(live_slots))
        if j is None:
            return ok
        ok = ok.copy()
        ok[live_slots[j]] = False
        self.stats.record_fault("nan")
        return ok

    def _req_key(self, req):
        # key for token i of request r depends only on (seed, i): sampling
        # is reproducible across scheduling orders and preemptions (and
        # i counts the token the launch in flight samples: ``_ngen``)
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                 self._ngen(req))
        return np.asarray(key, np.uint32)



# graft-lint import-of-engine hook: PT_ANALYSIS=strict refuses to import a
# serving module whose source carries ERROR-severity tracer hazards (the
# default 'off' mode is a single flag read).
from ..analysis import enforce_import as _enforce_import  # noqa: E402

_enforce_import(__name__, __file__)
