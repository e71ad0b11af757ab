"""Batched serving engine: continuous slot-based batching with KV paging.

Requests enter a queue; a fixed-slot batch decodes in lockstep (one jit'd
decode step for the whole batch).  Freed slots are refilled from the queue
each iteration (continuous batching).  With KV paging, each admitted
slot's prefilled KV cache is paged through a ``TieredStore`` — packed to a
byte page, spilled to the cold tier, fetched back H2C, and installed from
the device-resident page — so the cache crosses the paper's memory path
before serving.  ``access_path`` picks the mechanism (DESIGN.md §5);
output is bit-exact across all of them.

Admission is *prefetch-pipelined* (DESIGN.md §3.3) and
*decode-overlapped* (DESIGN.md §6): an admitted slot whose page is still
in flight parks in a pending-install set instead of blocking the step,
the batch keeps decoding resident slots, and each step installs exactly
the slots whose fetch completion has settled.  Output is bit-exact
either way: a slot's tokens depend only on its own cache, never on when
neighbours joined the batch.

Since the serving split (DESIGN.md §10) the engine also supports:

* an ``AdmissionController`` (``admission=``) that takes over queue
  ordering each step — priority classes, per-tenant token quotas,
  KV-capacity-aware slot refill, and SLO-driven shedding on a
  virtual-time clock fed by the engine's measured decode cadence;
* a *shared* memory plane (``shared_path=`` + ``page_base=`` +
  ``total_pages=``): N fleet replicas ride one fabric, each owning the
  page range ``[page_base, page_base + batch_slots)`` — the fabric is
  one address space, the engines partition it;
* monotonic latency clocks end to end: TTFT, TPOT, queue wait
  (submit→admit) and e2e latency all come from one ``perf_counter``
  pair per request — never mixed with wall-clock ``time.time``;
* ``run_until_drained(deadline_s=)``: a wall-clock budget for open-loop
  runs, alongside the step budget.

Chaos mode (DESIGN.md §9) is unchanged: a ``RetryPolicy`` wraps every
cold-tier op, per-page checksums verify every fetch, and a request whose
paging op stays failed after retries and failover is *shed* —
``Request.failed`` carries the reason, the batch keeps decoding everyone
else — never an assert.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import cplane, obs
from repro.access.registry import create_path
from repro.faults.retry import RETRIABLE, RetryPolicy
from repro.kernels import ops
from repro.models import lm
from repro.models import transformer as T
from repro.rmem.store import TieredStore

# deprecated --kv-backend spellings -> access-path names
_KV_BACKEND_ALIAS = {"local": "xdma", "remote": "verbs"}


@functools.lru_cache(maxsize=None)
def _jitted_steps(cfg):
    """One jitted (prefill, decode, in-place decode) triple per config,
    shared by every engine in the process.  jax keys its compilation
    cache on function identity, so per-engine
    ``jax.jit(lm.make_*_step(cfg))`` wrappers recompile the same XLA
    program once per replica (and once per run): a 2-replica fleet would
    pay the whole compile bill twice.

    The in-place decode donates the batch cache (argument 2): the step
    writes its KV rows into the cache, and the caller rebinds the cache
    to the result and holds no leaf of the old tree (DESIGN.md §6.5).
    The plain decode is ``ServeEngine.decode``, the step a caller may
    wrap; both are named ``jit_decode``."""
    decode = lm.make_decode_step(cfg)
    return (jax.jit(lm.make_prefill_step(cfg)), jax.jit(decode),
            jax.jit(decode, donate_argnums=(2,)))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new: int = 16
    out_tokens: Optional[List[int]] = None
    t_submit: float = 0.0
    t_done: float = 0.0
    failed: Optional[str] = None       # rejection reason (engine kept going)
    # serving-frontend identity (DESIGN.md §10): which tenant submitted
    # it, its priority class (higher admits first), and its TTFT
    # deadline in seconds from submit (None = no per-request SLO)
    tenant: str = "default"
    priority: int = 0
    deadline_s: Optional[float] = None
    t_arrival: float = 0.0             # open-loop arrival time (virtual)
    # shared-prefix length (DESIGN.md §12): the first prefix_len prompt
    # tokens are a cross-request prefix (system prompt / template); a
    # paging engine with prefix_share=True dedups the slot's spilled
    # page against the shared base keyed by those tokens' bytes
    prefix_len: int = 0
    # monotonic lifecycle clocks (perf_counter, one coherent pair):
    # submit -> admit is queue wait, submit -> first token is TTFT,
    # first -> done over the remaining tokens is TPOT, submit -> done
    # is e2e latency.  Wall-clock t_submit/t_done stay for display only.
    t_submit_pc: float = 0.0
    t_admit_pc: float = 0.0
    t_first_pc: float = 0.0
    t_done_pc: float = 0.0

    def cost_tokens(self) -> int:
        """The admission/routing work unit: prefill tokens + decode
        budget."""
        return int(len(self.prompt)) + int(self.max_new)


def failure_kind(reason: str) -> str:
    """Classify a ``Request.failed`` reason string into the short kinds
    the result dict's ``rejected.reasons`` section counts by."""
    if reason.startswith("slo"):
        return "slo"
    if reason.startswith("quota"):
        return "quota"
    if "prompt length" in reason:
        return "overlong"
    if "store failed" in reason:
        return "kv_store"
    if "fetch failed" in reason:
        return "kv_fetch"
    return "other"


def summarize_requests(done: List[Request]) -> dict:
    """Split finished requests into served vs rejected (satellite of
    DESIGN.md §10): latency aggregates and goodput cover *served only*;
    shed/rejected requests land in a separate section with per-reason
    counts, so a policy that sheds half the load cannot masquerade as a
    latency win in the same aggregate it polluted."""
    served = [r for r in done if r.failed is None]
    failed = [r for r in done if r.failed is not None]
    reasons: Dict[str, int] = {}
    for r in failed:
        k = failure_kind(r.failed)
        reasons[k] = reasons.get(k, 0) + 1
    tokens = sum(len(r.out_tokens or ()) for r in served)
    lat = [r.t_done_pc - r.t_submit_pc for r in served
           if r.t_done_pc > 0.0] or [0.0]
    return {"served": served, "tokens": tokens,
            "e2e_s": [float(x) for x in lat],
            "rejected": {"count": len(failed), "reasons": reasons,
                         "rids": sorted(r.rid for r in failed)}}


def page_bytes_for(cfg, max_len: int) -> int:
    """Bytes of one packed single-request KV page for ``cfg`` — the page
    geometry every engine over a shared fabric must agree on."""
    template = T.init_cache(cfg, 1, max_len)
    return sum(l.nbytes for l in jax.tree.leaves(template))


def page_codec_for(cfg, max_len: int, codec: Optional[str]):
    """The engine's page codec (DESIGN.md §12): the PR-9 ``PageLayout``
    already knows every leaf's byte extent and dtype inside the packed
    page, so its leaves become the codec's typed segments — float KV
    leaves compress, integer counters pass through raw, and the same
    object keys the fused install's dequant epilogue.  ``None`` for
    ``codec in (None, "none")``."""
    if codec is None or codec == "none":
        return None
    from repro.rmem import codec as codecs
    single = jax.eval_shape(lambda: T.init_cache(cfg, 1, max_len))
    batch = jax.eval_shape(lambda: T.init_cache(cfg, 2, max_len))
    layout = ops.page_layout(single, batch, 2)
    segs = [codecs.Segment(sp.offset, sp.nbytes, sp.dtype)
            for sp in layout.leaves if sp.nbytes]
    return codecs.make_codec(codec, layout.page_bytes, segs)


class ServeEngine:
    def __init__(self, cfg, params, batch_slots: int = 4,
                 max_len: int = 256, access_path: Optional[str] = None,
                 kv_backend: Optional[str] = None,
                 kv_shards: int = 1, kv_replicas: int = 1,
                 kv_kill_step: Optional[int] = None,
                 kv_nodes: Optional[int] = None, kv_doorbell: int = 4,
                 overlap: bool = True, overlap_grace_s: float = 0.002,
                 kv_node_latency_s: float = 0.0,
                 kv_retry: Optional[RetryPolicy] = None,
                 kv_integrity: bool = False,
                 admission=None,
                 shared_path=None, page_base: int = 0,
                 total_pages: Optional[int] = None,
                 fused_install: bool = True,
                 kv_codec: str = "none",
                 prefix_share: bool = False,
                 prefix_pages: int = 8,
                 prefix_base: Optional[int] = None,
                 kv_capacity_bytes: Optional[int] = None,
                 name: str = "engine0"):
        if kv_backend is not None:
            warnings.warn(
                "ServeEngine(kv_backend=...) is deprecated; use "
                "access_path='xdma'|'qdma'|'verbs'|'auto'",
                DeprecationWarning, stacklevel=2)
            if access_path is None:
                access_path = _KV_BACKEND_ALIAS[kv_backend]
        if kv_nodes is not None:
            # the --kv-nodes era striped one verbs backend over N
            # memory nodes; membership is now the fabric's (sharded
            # members, each a whole path), so the flag folds into it
            warnings.warn(
                "ServeEngine(kv_nodes=...) is deprecated; use "
                "kv_shards=N (fabric membership)", DeprecationWarning,
                stacklevel=2)
            if kv_shards == 1:
                kv_shards = kv_nodes
        if kv_shards < 1:
            raise ValueError(f"kv_shards must be >= 1, got {kv_shards}")
        if not 1 <= kv_replicas <= max(kv_shards, 1):
            raise ValueError(f"kv_replicas={kv_replicas} must be in "
                             f"[1, kv_shards={kv_shards}]")
        if kv_kill_step is not None and kv_replicas < 2:
            raise ValueError(
                "kv_kill_step without replication would lose pages: "
                "use kv_replicas >= 2")
        if shared_path is not None and (kv_shards > 1 or
                                        kv_kill_step is not None):
            raise ValueError(
                "shared_path engines do not own fabric membership: "
                "build the fabric (and kill schedule) at the fleet "
                "layer instead")
        if access_path is None and (kv_shards > 1 or
                                    kv_kill_step is not None):
            # sharding implies paging: a library caller asking for a
            # fabric (or fault injection) must get one, not a silent
            # unsharded run — same default the CLI applies
            access_path = "xdma"
        self.cfg = cfg
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.name = name
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self.done: List[Request] = []
        self.prefill_1, self.decode, self._decode_in_place = \
            _jitted_steps(cfg)
        self._decode_own = self.decode
        self.caches = T.init_cache(cfg, batch_slots, max_len)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_left = np.zeros(batch_slots, np.int64)
        self.slot_pos = np.zeros(batch_slots, np.int64)
        self.cur_tokens = np.zeros((batch_slots, 1), np.int32)
        # KV paging: one page per slot holding the packed prefill cache.
        # Over a shared fabric (fleet mode) the engine's pages live at
        # [page_base, page_base + batch_slots) of the fabric's address
        # space — _pg() maps slot -> fabric page.
        self.pager: Optional[TieredStore] = None
        self.access_path = access_path
        self.page_base = page_base
        self.overlap = overlap
        # grace: before decoding with installs pending, give their
        # fetches this long to settle — a fetch faster than the grace
        # installs THIS step (degrading gracefully to the serial join),
        # a slower one overlaps with the decode instead of blocking it
        self.overlap_grace_s = overlap_grace_s
        # admitted-but-nonresident slots: prefilled, spilled, fetch in
        # flight — decode keeps running; each entry installs the step its
        # page lands (slot -> (req, first_tok, leaves, treedef))
        self._pending_install: Dict[int, Tuple] = {}
        self.overlap_installs = 0       # installs that joined a settled
        self.blocking_installs = 0      # ... vs had to block/join inline
        # fused install/spill path (DESIGN.md §11): route the cache
        # scatter/gather through the PageLayout kernels instead of the
        # per-leaf slice/.at[].set chain — bit-exact either way
        self.fused_install = fused_install
        self._layout = None             # PageLayout, built lazily
        # capacity multipliers (DESIGN.md §12): the tier-boundary codec
        # and cross-request prefix sharing.  Both default off; the
        # default-off paths are byte-compatible with the PR-9 engine.
        self.kv_codec = kv_codec
        self.prefix_share = prefix_share
        self.prefix_pages = prefix_pages if prefix_share else 0
        # EWMA of the delta/encoded size ratio store_dedup actually
        # achieved — the admission-layer estimate of a shared request's
        # effective page cost (prior 0.5 until the first sample lands)
        self._share_ratio = 0.5
        self.install_fused = 0          # slots installed via the kernel
        self.install_fallback = 0       # ... vs the per-leaf chain
        self.install_hops_saved = 0     # per-leaf D2H readbacks avoided
        self._admit_spills: List[int] = []   # pages spilled this admit
        self.kv_shards = kv_shards
        self.kv_replicas = kv_replicas
        self.kv_kill_step = kv_kill_step
        # fault handling (§9): the retry policy + checksum plane live in
        # whichever layer owns replica routing — the fabric when sharded
        # (replica fallback needs the ring), the tier store otherwise
        self.kv_retry = kv_retry
        self.kv_integrity = kv_integrity
        self.shed_requests = 0
        self.fabric = None                  # ShardedPath when sharded
        self.fabric_mgr = None
        self.killed_member: Optional[str] = None
        self.kill_step: Optional[int] = None
        self._step_no = 0
        # serving frontend (§10): optional admission controller (owns
        # queue ordering + shedding policy) and the routing work counter
        # the fleet reads (tokens submitted but not yet finished/shed)
        self.admission = admission
        self._outstanding = 0
        # per-request latency distributions (always on: one record per
        # request lifecycle event, nowhere near the hot decode loop).
        # TTFT = submit -> first token (prefill + paging + queueing);
        # TPOT = (done - first) / (tokens - 1), the decode cadence;
        # queue wait = submit -> admit, the open-loop queueing term.
        self.ttft_hist = obs.LogHistogram()
        self.tpot_hist = obs.LogHistogram()
        self.queue_wait_hist = obs.LogHistogram()
        # fabric membership events drained per step and stamped with the
        # decode step they landed in (when the kill hit, relative to
        # decode progress — satellite of DESIGN.md §8)
        self.fabric_events: List[dict] = []
        # pages are staged on device as the layout's widest words
        # (PageLayout.word_dtype); the bytes are the same
        words = self._install_layout().word_dtype
        if shared_path is not None:
            if total_pages is None:
                total_pages = page_base + batch_slots
            page_bytes = page_bytes_for(cfg, max_len)
            self._cache_template = None
            pool = ()
            if prefix_share:
                if prefix_base is None:
                    raise ValueError(
                        "prefix_share over a shared plane needs "
                        "prefix_base= (the fleet sizes the base pool "
                        "past every replica's page range)")
                pool = range(prefix_base, prefix_base + prefix_pages)
            # the path is the fleet's: one retry/integrity plane lives
            # inside it (ShardedPath) or above it at the tier, exactly
            # like the self-built case below
            fabric_owned = getattr(shared_path, "_members", None) \
                is not None
            self.pager = TieredStore(
                n_pages=total_pages,
                page_shape=(page_bytes // words.itemsize,),
                dtype=words.name, n_hot_slots=batch_slots, path=shared_path,
                retry=None if fabric_owned else kv_retry,
                integrity=kv_integrity,
                codec=page_codec_for(cfg, max_len, kv_codec),
                shared_pool=pool, capacity_bytes=kv_capacity_bytes)
        elif access_path is not None:
            self._cache_template = T.init_cache(cfg, 1, max_len)
            page_bytes = sum(l.nbytes
                             for l in jax.tree.leaves(self._cache_template))
            codec_obj = page_codec_for(cfg, max_len, kv_codec)
            # the cold tier is sized in *physical* (encoded) bytes: the
            # codec's compression is real fabric capacity, and the
            # byte-accurate path model rates transfers at what actually
            # moves (DESIGN.md §12)
            phys_bytes = codec_obj.encoded_bytes if codec_obj is not None \
                else page_bytes
            n_tier_pages = batch_slots + self.prefix_pages
            if kv_shards > 1:
                # the sharded memory plane: N member paths (each a full
                # access path) behind one consistent-hash ShardedPath —
                # TieredStore stays shard-oblivious, both hops ride it
                from repro.fabric import FabricManager
                apath = create_path(
                    "fabric", member=access_path, shards=kv_shards,
                    replicas=kv_replicas, n_pages=n_tier_pages,
                    page_bytes=phys_bytes, n_channels=2, n_nodes=1,
                    doorbell_batch=kv_doorbell,
                    node_latency_s=kv_node_latency_s,
                    retry=kv_retry, integrity=kv_integrity)
                self.fabric = apath
                self.fabric_mgr = FabricManager(apath)
            else:
                # registry factories drop kwargs their path doesn't take
                apath = create_path(access_path, n_pages=n_tier_pages,
                                    page_bytes=phys_bytes, n_channels=2,
                                    n_nodes=1,
                                    doorbell_batch=kv_doorbell,
                                    node_latency_s=kv_node_latency_s)
            # one retry layer, not two: with the fabric retrying (and
            # failing over) internally, a tier-level policy on top would
            # multiply attempts for ops the fabric already gave up on
            self.pager = TieredStore(
                n_pages=n_tier_pages,
                page_shape=(page_bytes // words.itemsize,),
                dtype=words.name,
                n_hot_slots=batch_slots, path=apath,
                retry=kv_retry if self.fabric is None else None,
                integrity=kv_integrity, codec=codec_obj,
                shared_pool=range(batch_slots,
                                  batch_slots + self.prefix_pages),
                capacity_bytes=kv_capacity_bytes)

    # -- page-range partitioning over a shared plane ---------------------
    def _pg(self, slot: int) -> int:
        """This engine's fabric page for ``slot`` (identity when the
        engine owns the whole plane)."""
        return self.page_base + slot

    def submit(self, req: Request) -> None:
        req.t_submit = time.time()
        req.t_submit_pc = time.perf_counter()
        req.out_tokens = []
        self._outstanding += req.cost_tokens()
        obs.async_begin("serve.request", req.rid,
                        prompt_len=len(req.prompt), max_new=req.max_new)
        self.queue.put(req)

    def outstanding_tokens(self) -> int:
        """Work this engine has accepted but not finished (prefill +
        decode tokens of queued, backlogged, pending and active
        requests) — the fleet router's least-outstanding-work metric."""
        return self._outstanding

    def backlog_size(self) -> int:
        return 0 if self.admission is None else len(self.admission.backlog)

    def kv_free_pages(self) -> int:
        """Free KV page capacity this engine can admit into: slots that
        are unoccupied AND whose fabric page is neither resident nor
        mid-fetch in the ``TieredStore`` — what admission asks before
        accepting, so a page still draining from a previous occupant
        (or an abandoned prefetch) blocks re-admission of its slot."""
        if self.pager is None:
            return sum(1 for s in range(self.B)
                       if self.slot_req[s] is None
                       and s not in self._pending_install)
        free = 0
        for s in range(self.B):
            if self.slot_req[s] is not None or s in self._pending_install:
                continue
            p = self._pg(s)
            if p in self.pager.slot_of_page or p in self.pager._prefetch:
                continue
            free += 1
        byte_free = self.pager.free_cold_bytes()
        if byte_free is not None:
            # soft physical-byte budget (§12): admission refills against
            # *effective* capacity — compressed/deduped pages leave more
            # budget than their logical size suggests
            free = min(free, byte_free // max(self.pager.phys_page_bytes,
                                              1))
        return free

    def kv_page_cost(self, req: Request) -> float:
        """Effective KV page cost of admitting ``req`` (the admission
        controller's ``kv_cost`` hook): 1.0 for a standalone page; for a
        shared-prefix request whose base is already published, the EWMA
        of the delta/encoded ratio ``store_dedup`` has been achieving —
        so a half-shared workload admits ~2x the requests per unit of
        fabric budget."""
        if self.pager is None or not self.prefix_share or \
                req.prefix_len <= 0:
            return 1.0
        key = req.prompt[:req.prefix_len].tobytes()
        if self.pager.lookup_shared(key) is None:
            return 1.0          # first writer publishes a full base
        return self._share_ratio

    def _install_layout(self):
        """The engine's ``PageLayout`` (DESIGN.md §11), built once per
        engine from the cache treedef via ``eval_shape`` (no cache
        materialization) and shared by the fused install, spill and slot
        kernels."""
        if self._layout is None:
            single = jax.eval_shape(
                lambda: T.init_cache(self.cfg, 1, self.max_len))
            batch = jax.eval_shape(
                lambda: T.init_cache(self.cfg, self.B, self.max_len))
            self._layout = ops.page_layout(single, batch, self.B)
        return self._layout

    def _slot_cache_set(self, slot: int, new_caches) -> None:
        """Write one slot's prefilled (B=1) cache into the batch cache tree.

        The batch axis is located structurally: it is the axis where the
        batch leaf has size ``B`` and the single-request leaf has size 1
        (stacked group caches are (G, B, ...), tail caches (B, ...), and
        per-layer "len" scalars have no batch axis at all).  With
        ``fused_install`` the whole update runs as one jitted donated
        scatter keyed on the PageLayout's slot-axis map, instead of the
        unjitted per-leaf ``.at[].set`` loop re-dispatched every admit.
        """
        flat_b, treedef = jax.tree.flatten(self.caches)
        flat_o = jax.tree.leaves(new_caches)
        if self.fused_install:
            out = ops.install_slot(self._install_layout(), flat_b,
                                   flat_o, slot, donate=True)
            self.caches = jax.tree.unflatten(treedef, out)
            return
        out = []
        for b, o in zip(flat_b, flat_o):
            ax = next((i for i, (x, y) in enumerate(zip(b.shape, o.shape))
                       if x == self.B and y == 1), None)
            if ax is None:             # "len" counters: no batch axis
                out.append(jnp.maximum(b, o))
                continue
            idx = [slice(None)] * b.ndim
            idx[ax] = slot
            src_idx = [slice(None)] * o.ndim
            src_idx[ax] = 0
            out.append(b.at[tuple(idx)].set(o[tuple(src_idx)]))
        self.caches = jax.tree.unflatten(treedef, out)

    def _page_store(self, slot: int, req: Request, leaves) -> None:
        """Pack a slot's prefilled cache to one byte page, spill it to the
        cold tier, and queue its *prefetch* — the whole admission round's
        fetches are issued in one batched call from ``_admit``, and the
        async fetch (one-sided verbs or host gather) runs while admission
        moves on to other slots.

        Fused path: the pack runs as one on-device gather kernel and
        crosses C2H as ONE readback of the packed page; the per-leaf
        chain pays one blocking ``np.asarray`` per leaf plus a host
        ``np.concatenate``.  Identical bytes either way.  The pack and
        the spill are the ``kv.pack`` and ``kv.spill`` spans, each
        carrying the page's logical bytes.
        """
        layout = self._install_layout()
        with obs.span("kv.pack", rid=req.rid, bytes=layout.page_bytes):
            if self.fused_install:
                packed = ops.pack_page(layout, leaves)
                self.install_hops_saved += max(0, len(leaves) - 1)
            else:
                packed = np.concatenate(
                    [np.asarray(l).reshape(-1).view(np.uint8)
                     for l in leaves])
            packed = packed.view(layout.word_dtype)
        with obs.span("kv.spill", rid=req.rid, bytes=layout.page_bytes):
            if self.prefix_share and req.prefix_len > 0:
                # dedup the spill against the shared base for this
                # prompt prefix (§12): first writer publishes, later
                # writers store only the block delta — bit-exact
                # reconstruction, so tokens are invariant to sharing
                key = req.prompt[:req.prefix_len].tobytes()
                ratio = self.pager.store_dedup(self._pg(slot), packed, key)
                self._share_ratio += 0.5 * (ratio - self._share_ratio)
            else:
                self.pager.write_page(self._pg(slot), packed)
        self._admit_spills.append(self._pg(slot))

    def _flush_spill_prefetch(self) -> None:
        """Start every page prefetch this admission round queued, in one
        call — the miss pipeline batches them into doorbell-depth fetch
        groups, so K admitted slots pay one batched issue (and one
        staged H2C per group on the fused path), not K."""
        if self._admit_spills:
            self.pager.prefetch(self._admit_spills)
            self._admit_spills = []

    def _page_fetch(self, slot: int, leaves, treedef):
        """Join the slot's in-flight prefetch (``ensure`` finds the bytes
        already staged) and unpack the device-resident page into cache
        leaves.  Bit-exact by construction, so serving output is invariant
        to the backend."""
        dev_page = self.pager.ensure([self._pg(slot)])[self._pg(slot)]
        k = dev_page.dtype.itemsize
        out, off = [], 0
        for l in leaves:
            piece = jax.lax.slice(dev_page, (off // k,),
                                  ((off + l.nbytes) // k,))
            out.append(piece.view(l.dtype).reshape(l.shape))
            off += l.nbytes
        return jax.tree.unflatten(treedef, out)

    def _reject_overlong(self, req: Request, P: int) -> None:
        req.failed = (f"prompt length {P} >= engine max_len "
                      f"{self.max_len}")
        req.t_done = time.time()
        req.t_done_pc = time.perf_counter()
        self._outstanding -= req.cost_tokens()
        self.done.append(req)
        obs.async_end("serve.request", req.rid, rejected=True)

    def _start_request(self, s: int, req: Request) -> None:
        """Admit ``req`` into slot ``s``: prefill, then either install
        inline (no paging) or spill + prefetch and park pending-install.
        Records the queue-wait histogram sample (submit -> admit)."""
        req.t_admit_pc = time.perf_counter()
        qw = req.t_admit_pc - req.t_submit_pc
        self.queue_wait_hist.record(qw)
        if obs.metrics.live():
            reg = obs.default_registry()
            reg.histogram("serve.queue_wait_s").record(qw)
            reg.histogram(
                f"serve.tenant.{req.tenant}.queue_wait_s").record(qw)
        P = len(req.prompt)
        batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
        if self.cfg.attention is not None and \
                self.cfg.attention.mrope_sections is not None:
            batch["pos"] = jnp.broadcast_to(
                jnp.arange(P, dtype=jnp.int32)[None, :, None], (1, P, 3))
        with obs.span("serve.prefill", rid=req.rid, slot=s,
                      prompt_len=P):
            with obs.span("model.prefill", rid=req.rid):
                caches1 = T.init_cache(self.cfg, 1, self.max_len)
                caches1, logits = self.prefill_1(self.params, batch,
                                                 caches1)
                tok = int(jnp.argmax(logits[0]))
            if self.pager is not None:
                leaves, treedef = jax.tree.flatten(caches1)
                try:
                    self._page_store(s, req, leaves)
                except RETRIABLE as e:
                    self._shed(req, f"kv page store failed: {e}",
                               slot=s)
                    return
                self._pending_install[s] = (req, tok, leaves, treedef)
            else:
                self._install(s, req, tok, caches1)

    def _admit(self) -> None:
        """Fill free slots from the queue (continuous batching).

        Without a controller this is the legacy FIFO-until-full refill:
        pop the queue per free slot, rejecting over-long prompts inline.
        With an ``AdmissionController`` the ingress queue first drains
        into the controller's priority backlog, then the controller
        decides — against free slots, free KV pages, quotas and the SLO
        prediction — which requests admit now, which wait, and which
        shed early (``Request.failed = "slo"``/``"quota"``).

        When paging, each admitted request prefills, spills its packed
        cache cold, and starts the page's *prefetch*; the slot then goes
        to the pending-install set — ``_install_ready`` moves it into the
        decode batch once (``overlap=True``) or regardless of whether
        (``overlap=False``) its fetch has settled.  Slot k's cold fetch
        is in flight while slot k+1 is still prefilling AND while the
        resident batch keeps decoding, so paging latency hides behind
        both admission work and the decode cadence.
        """
        free = [s for s in range(self.B)
                if self.slot_req[s] is None
                and s not in self._pending_install]
        if self.admission is None:
            for s in free:
                req = None
                while req is None:
                    try:
                        cand = self.queue.get_nowait()
                    except queue.Empty:
                        break
                    P = len(cand.prompt)
                    if P >= self.max_len:
                        self._reject_overlong(cand, P)
                        continue
                    req = cand
                if req is None:
                    break
                self._start_request(s, req)
            self._flush_spill_prefetch()
            return
        # controller path: ingress -> backlog (overlong rejected at the
        # door: no policy can fix a prompt the engine cannot hold)
        while True:
            try:
                cand = self.queue.get_nowait()
            except queue.Empty:
                break
            P = len(cand.prompt)
            if P >= self.max_len:
                self._reject_overlong(cand, P)
                continue
            self.admission.enqueue(cand)
        admits, sheds = self.admission.select(
            free_slots=len(free), kv_free=self.kv_free_pages(),
            batch_slots=self.B,
            kv_cost=self.kv_page_cost
            if (self.pager is not None and self.prefix_share) else None)
        for req, reason in sheds:
            self._shed(req, reason)
        for s, req in zip(free, admits):
            self._start_request(s, req)
        self._flush_spill_prefetch()

    def _install(self, s: int, req: Request, tok: int, caches1) -> None:
        self._slot_cache_set(s, caches1)
        self._install_meta(s, req, tok)

    def _install_meta(self, s: int, req: Request, tok: int) -> None:
        """Post-scatter slot bookkeeping: the part of an install that is
        per-request metadata, split out so the fused group path can run
        ONE scatter kernel for many slots and then account each."""
        self.slot_req[s] = req
        self.slot_left[s] = req.max_new - 1
        self.slot_pos[s] = len(req.prompt)
        self.cur_tokens[s, 0] = tok
        req.out_tokens.append(tok)
        # first token lands here: TTFT covers queueing + prefill + the
        # whole paging round trip (spill, cold fetch, H2C, install)
        req.t_first_pc = time.perf_counter()
        ttft = req.t_first_pc - req.t_submit_pc
        self.ttft_hist.record(ttft)
        if obs.metrics.live():
            reg = obs.default_registry()
            reg.histogram("serve.ttft_s").record(ttft)
            reg.histogram(f"serve.tenant.{req.tenant}.ttft_s").record(ttft)
        if obs.trace.enabled():
            obs.instant("serve.first_token", rid=req.rid, slot=s,
                        ttft_s=ttft)

    def _shed(self, req: Request, reason: str,
              slot: Optional[int] = None) -> None:
        """Degrade instead of crash (§9): a paging op that stayed failed
        after retries and replica failover — or an admission policy
        decision (§10: ``"slo"``/``"quota"``) — sheds THIS request;
        ``Request.failed`` carries the reason and the batch keeps
        decoding everyone else.  Survivors stay bit-exact: a slot's
        tokens depend only on its own cache."""
        req.failed = reason
        req.t_done = time.time()
        req.t_done_pc = time.perf_counter()
        self._outstanding -= req.cost_tokens()
        self.done.append(req)
        self.shed_requests += 1
        if slot is not None and self.pager is not None:
            self._pending_install.pop(slot, None)
            self.pager.drop_prefetch(self._pg(slot))
            try:
                self.pager.release(self._pg(slot), writeback=False)
            except Exception:
                pass        # the page is being abandoned either way
            try:
                self.pager.discard_cold(self._pg(slot))
            except Exception:
                pass
        if obs.trace.enabled():
            obs.instant("serve.shed", rid=req.rid, reason=reason,
                        tenant=req.tenant)
        if obs.metrics.live():
            reg = obs.default_registry()
            reg.counter("serve.shed_requests").inc()
            reg.counter(
                f"serve.tenant.{req.tenant}.shed_requests").inc()
        obs.async_end("serve.request", req.rid, shed=True)

    def _install_ready(self, have_active: bool) -> None:
        """Move pending-install slots whose page fetch has settled into
        the decode batch.

        ``overlap=True``: only settled fetches install; with nothing else
        to decode the engine blocks on ``cplane.wait_any`` across ALL
        pending fetches — waking on the first page to land, whichever
        path or backend it came from — and installs at least one slot so
        the loop always progresses.  ``overlap=False`` (the serial
        baseline): every pending slot installs now, joining its fetch
        inline exactly like the pre-cplane two-phase admission.
        """
        if not self._pending_install:
            return
        if not self.overlap:
            ready = sorted(self._pending_install)
            self.blocking_installs += len(ready)
        else:
            pending = sorted(self._pending_install)
            with obs.span("serve.install_wait", pending=len(pending)):
                ready = self._settled(pending, have_active)
            if ready:
                self.overlap_installs += len(ready)
            elif not have_active:
                # non-reactive backend (or nothing within 60s): join one
                # fetch inline so the loop always progresses
                ready = [pending[0]]
                self.blocking_installs += 1
        if not ready:
            return
        if self.fused_install:
            self._install_ready_fused(ready)
        else:
            for s in ready:
                self._install_one(s)

    def _settled(self, pending: List[int], have_active: bool) -> List[int]:
        """The pending slots whose fetch has settled.  If none has yet:
        with other slots decodable, grant a short grace (a fast fetch
        installs this step, a slow one overlaps the decode); with nothing
        decodable, block until the FIRST page lands, whichever it is.
        Only reactive handles can settle on their own — a legacy eager
        PendingIO never will, so waiting on one would just burn the full
        timeout before the inline join."""
        ready = [s for s in pending if self.pager.fetch_ready(self._pg(s))]
        if ready:
            return ready
        cs = [c for s in pending
              if (c := self.pager.fetch_completion(self._pg(s))) is not None
              and getattr(c, "reactive", True)]
        if cs:
            try:
                cplane.wait_any(cs, timeout=self.overlap_grace_s
                                if have_active else 60.0)
            except cplane.CompletionTimeout:
                pass
        return [s for s in pending if self.pager.fetch_ready(self._pg(s))]

    def _install_one(self, s: int) -> None:
        """Per-leaf reference install for one slot: join its fetch, slice
        the device page back into cache leaves, scatter leaf by leaf."""
        req, tok, leaves, treedef = self._pending_install.pop(s)
        with obs.span("serve.install", rid=req.rid, slot=s,
                      path="fallback"):
            try:
                caches1 = self._page_fetch(s, leaves, treedef)
            except RETRIABLE as e:
                self._shed(req, f"kv page fetch failed: {e}", slot=s)
                return
            self._install(s, req, tok, caches1)
            self.install_fallback += 1
            if obs.metrics.live():
                obs.default_registry().counter(
                    "serve.install_fallback").inc()

    def _install_ready_fused(self, ready: List[int]) -> None:
        """Install a whole group of settled slots through ONE fused
        scatter: ``ensure_packed`` hands back each page's staged
        ``(buffer, row)`` pair unsplit, and a single ``install_pages``
        call scatters every leaf of every page into the batch cache.  A
        group-level paging failure degrades to the per-slot reference
        path so only the slots whose fetch actually failed shed."""
        try:
            packed = self.pager.ensure_packed(
                [self._pg(s) for s in ready])
        except RETRIABLE:
            for s in ready:
                self._install_one(s)
            return
        meta = [self._pending_install.pop(s) for s in ready]
        # split by staged representation: encoded groups carry the
        # codec's physical bytes to device (the H2C already moved fewer
        # bytes) and install through the dequant epilogue; raw groups
        # (codec off, or delta pages materialized host-side) install
        # through the byte-identical PR-9 program
        enc = [s for s in ready if self.pager.staged_encoded(self._pg(s))]
        raw = [s for s in ready if s not in enc]
        with obs.span("serve.install", path="fused", slots=len(ready),
                      rids=[m[0].rid for m in meta]):
            flat_b, treedef = jax.tree.flatten(self.caches)
            for group, codec in ((raw, None), (enc, self.pager.codec)):
                if not group:
                    continue
                entries = [packed[self._pg(s)] for s in group]
                flat_b = ops.install_pages(self._install_layout(), flat_b,
                                           entries, group, donate=True,
                                           codec=codec)
            self.caches = jax.tree.unflatten(treedef, flat_b)
        self.install_fused += len(ready)
        if obs.metrics.live():
            obs.default_registry().counter(
                "serve.install_fused").inc(len(ready))
        for s, (req, tok, _leaves, _treedef) in zip(ready, meta):
            self._install_meta(s, req, tok)

    def _maybe_kill_node(self) -> None:
        """Fail one fabric member at the configured step (fault
        injection): reads fail over to replicas immediately and the
        manager re-replicates onto the survivor ring — decode output
        must stay bit-exact through it."""
        if self.fabric_mgr is None or self.kv_kill_step is None or \
                self.killed_member is not None or \
                self._step_no < self.kv_kill_step:
            return
        victim = self.fabric.alive_members()[-1]
        if obs.trace.enabled():
            obs.instant("serve.kill", member=victim, step=self._step_no)
        repair = self.fabric_mgr.kill(victim)
        self.killed_member = victim
        self.kill_step = self._step_no
        self.kill_repair = repair

    def _finish(self, req: Request) -> None:
        req.t_done = time.time()
        req.t_done_pc = time.perf_counter()
        self._outstanding -= req.cost_tokens()
        self.done.append(req)
        n = len(req.out_tokens)
        if req.t_first_pc > 0.0 and n > 1:
            tpot = (req.t_done_pc - req.t_first_pc) / (n - 1)
            self.tpot_hist.record(tpot)
            if obs.metrics.live():
                reg = obs.default_registry()
                reg.histogram("serve.tpot_s").record(tpot)
                reg.histogram(
                    f"serve.tenant.{req.tenant}.tpot_s").record(tpot)
        if self.admission is not None:
            self.admission.observe_finish(req)
        obs.async_end("serve.request", req.rid, tokens=n)

    def _drain_fabric_events(self) -> None:
        """Stamp the fabric's membership events (fail / epoch / ring
        flip / repair) with the decode step they landed in — the serve
        result's answer to "when did the kill hit, relative to decode
        progress"."""
        if self.fabric is None:
            return
        for ev in self.fabric.drain_events():
            ev["step"] = self._step_no
            self.fabric_events.append(ev)

    def step(self) -> int:
        """One batched decode step; returns #active slots.  The whole
        step is the ``serve.step`` span; its children split admission,
        the wait for page fetches, install, the decode's dispatch and
        its sync, and retirement (DESIGN.md §8.1)."""
        self._step_no += 1
        t_step0 = time.perf_counter()
        with obs.span("serve.step", step=self._step_no) as span:
            self._maybe_kill_node()
            with obs.span("serve.admit"):
                self._admit()
            if self.pager is not None:
                have_active = any(r is not None for r in self.slot_req)
                self._install_ready(have_active)
            self._drain_fabric_events()
            active = [s for s in range(self.B)
                      if self.slot_req[s] is not None]
            span.set(active=len(active))
            if not active:
                return 0
            nxt = self._decode(len(active))
            if self.admission is not None:
                # the virtual-time clock: the full step duration
                # (admission + install + decode — what a queued request
                # actually waits through per step) advances admission's
                # clock and feeds the cadence its TTFT prediction
                # multiplies queue depth by
                self.admission.observe_step(time.perf_counter() - t_step0,
                                            active=len(active))
            self._retire(active, nxt)
        return len(active)

    def _decode(self, n_active: int) -> np.ndarray:
        """One jitted decode of the whole batch; the next token of every
        slot, on the host."""
        with obs.span("serve.decode_step", step=self._step_no,
                      active=n_active):
            with obs.span("serve.decode.dispatch"):
                pos = jnp.asarray(self.slot_pos, jnp.int32)[:, None]
                batch = {"tokens": jnp.asarray(self.cur_tokens)}
                if self.cfg.attention is not None and \
                        self.cfg.attention.mrope_sections is not None:
                    batch["pos"] = jnp.broadcast_to(pos[..., None],
                                                    (self.B, 1, 3))
                else:
                    batch["pos"] = pos
                # the engine's own decode consumes the cache in place; a
                # step a caller put in its place runs as given, since it
                # may hand back the cache it was given
                step = self._decode_in_place \
                    if self.decode is self._decode_own else self.decode
                old = jax.tree.leaves(self.caches)
                self.caches, logits = step(self.params, batch, self.caches)
                nxt = jnp.argmax(logits, axis=-1)
                if obs.metrics.live():
                    # a silent loss of donation reads 0
                    obs.default_registry().counter(
                        "serve.decode.kv_donated_bytes").inc(
                            sum(l.nbytes for l in old if l.is_deleted()))
            with obs.span("serve.decode.sync"):
                return np.asarray(nxt, np.int32)

    def _retire(self, active: List[int], nxt: np.ndarray) -> None:
        """Append each active slot's token; finish and free the slots
        whose budget ran out (their pages leave the tier store)."""
        with obs.span("serve.retire") as span:
            finished = 0
            for s in active:
                tok = int(nxt[s])
                req = self.slot_req[s]
                req.out_tokens.append(tok)
                self.slot_pos[s] += 1
                self.slot_left[s] -= 1
                if self.slot_left[s] <= 0:
                    finished += 1
                    self._finish(req)
                    self.slot_req[s] = None
                    if self.pager is not None:
                        self.pager.release(self._pg(s))
                        # the retiring request's cold bytes return to the
                        # soft budget (and its delta's base ref drops) —
                        # what admission's refill draws against (§12)
                        self.pager.discard_cold(self._pg(s))
                else:
                    self.cur_tokens[s, 0] = tok
            span.set(finished=finished)

    def idle(self) -> bool:
        """True when nothing is queued, backlogged, pending or active."""
        return (self.queue.empty() and not self._pending_install
                and self.backlog_size() == 0
                and all(r is None for r in self.slot_req))

    def undrained_count(self) -> int:
        return (self.queue.qsize()
                + self.backlog_size()
                + sum(r is not None for r in self.slot_req)
                + len(self._pending_install))

    def run_until_drained(self, max_steps: int = 10000,
                          deadline_s: Optional[float] = None) -> int:
        """Step until every request finishes, or a budget runs out.

        Two budgets: ``max_steps`` bounds decode steps (the closed-loop
        spelling) and ``deadline_s`` bounds wall-clock seconds (the
        open-loop spelling — an arrival-driven run should stop after a
        time horizon, not a step count).  Either alone or both together.

        Returns the number of undrained requests (0 on a clean drain:
        queue empty, backlog empty, no active slots, no pending
        installs).  A nonzero return — a budget ran out with work
        left — also warns, naming both budgets, instead of the old
        silent truncation.
        """
        t0 = time.monotonic()
        steps = 0
        while steps < max_steps and \
                (deadline_s is None or time.monotonic() - t0 < deadline_s):
            steps += 1
            if self.step() == 0 and self.idle():
                return 0
        left = self.undrained_count()
        if left:
            elapsed = time.monotonic() - t0
            warnings.warn(
                f"run_until_drained: {left} requests still undrained "
                f"after max_steps={max_steps} (used {steps}) and "
                f"deadline_s={deadline_s} (elapsed {elapsed:.3f}s)",
                RuntimeWarning, stacklevel=2)
        return left
