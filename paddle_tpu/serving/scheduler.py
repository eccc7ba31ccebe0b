"""Continuous-batching scheduler (host-side policy, no device code).

Static batching decodes a fixed batch until EVERY row finishes: short
requests pad out to the longest, and arrivals wait for the next batch. Here
requests flow through three states instead:

    queued ──admit──▶ prefill ──first token──▶ running ──eos/len──▶ finished

and the engine calls one `Scheduler` tick per decode step, so:

  * admission happens BETWEEN decode steps — a new request joins the
    running batch as soon as a slot and KV blocks are available;
  * prefill is chunked and interleaved with decode (one bounded chunk per
    tick), so a long prompt cannot stall the running batch's tokens for
    more than one chunk's worth of compute;
  * a finished sequence's blocks are freed (and its slot reopened)
    IMMEDIATELY, before the next admission check.

Admission uses worst-case KV reservation: a request is admitted only when
`blocks_for(min(prompt + max_new_tokens, max_model_len))` blocks fit beside
every admitted request's reservation. Decode-time block appends therefore
NEVER fail mid-flight — no preemption/swap machinery is needed (the trade
is admission conservatism, i.e. occupancy, not correctness).

The same reservation covers SPECULATIVE (up-to-k-token) ticks: the engine
caps every draft at the remaining `max_new_tokens` budget and at
`max_model_len - 1 - current_len`, so a verify window can never commit a
token past the reserved worst case, and rollback only ever shrinks usage
back toward it (BlockAllocator.rollback never trims below the
reservation).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

from ..observability.registry import counter as _counter, gauge as _gauge

_ADMITTED = _counter("serving_requests_admitted_total",
                     "Requests admitted into the running batch.",
                     always=True)
_FINISHED = _counter("serving_requests_finished_total",
                     "Requests finished (by reason).",
                     labelnames=("reason",), always=True)
_QUEUED = _gauge("serving_queue_depth", "Requests waiting for admission.",
                 always=True)
_RUNNING = _gauge("serving_running_sequences",
                  "Sequences in prefill or decode.", always=True)

_req_counter = itertools.count()


class Request:
    """One generation request and its lifecycle telemetry. Timestamps are
    time.monotonic(); the engine fills them as the request moves through
    the pipeline (queue time = prefill_start - arrival, TTFT =
    first_token - arrival, first_token being when the first token's value
    reached the host)."""

    def __init__(self, prompt: List[int], max_new_tokens: int = 16,
                 temperature: float = 0.0, eos_token_id: Optional[int] = None,
                 request_id: Optional[str] = None, tier: str = "default",
                 trace_ctx: Optional[dict] = None,
                 prefill_only: bool = False):
        self.request_id = (request_id if request_id is not None
                           else f"req-{next(_req_counter)}")
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        # admission tier: the SLO-metric label (one class today; the
        # fleet router's priority tiers plug in here)
        self.tier = str(tier) if tier else "default"
        # per-request lifecycle trace, attached by the engine at submit
        # when span recording is on (serving/observability.RequestTrace)
        self.trace = None
        # distributed trace context stamped by the FleetRouter: which
        # fleet request / attempt / cause this engine-level placement
        # serves — RequestTrace inherits it so every span is attributed
        self.trace_ctx = dict(trace_ctx) if trace_ctx else None
        # disaggregated serving: compute + register + keep the prompt's KV
        # blocks, then finish with reason "prefill_complete" WITHOUT
        # sampling a first token — the blocks are exported to a decode
        # replica instead of decoded locally
        self.prefill_only = bool(prefill_only)
        self.output_tokens: List[int] = []
        self.state = "queued"
        self.finish_reason: Optional[str] = None
        self.slot: Optional[int] = None
        self.arrival_time = time.monotonic()
        self.prefill_start: Optional[float] = None
        self.first_token_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        # engine-owned prefill progress (tokens of prompt already run)
        self.prefill_pos = 0
        self.prefix_matched = 0       # prompt tokens served from the cache
        self._cow_src = None          # shared block forked at admission
        self._ws_caches = None        # contiguous prefill workspace
        self._pending_n = 0           # sampled tokens in flight, not fetched
        self._reserved_blocks = 0
        # self-speculation state, attached by the engine when spec is on
        # (greedy requests only); kept after finish for telemetry
        self._drafter = None          # speculative.NgramDrafter
        self._spec = None             # speculative.SpecState
        self._done = threading.Event()  # set at finish (HTTP waiters)
        self._progress = threading.Event()  # pulsed per token fetch

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def wait_progress(self, timeout: Optional[float] = None) -> bool:
        """Block until more output tokens were fetched (or the request
        finished). Streaming handlers clear + re-wait in a loop."""
        return self._progress.wait(timeout)

    # -- telemetry --------------------------------------------------------
    def queue_seconds(self) -> Optional[float]:
        if self.prefill_start is None:
            return None
        return self.prefill_start - self.arrival_time

    def ttft_seconds(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    def decode_tokens_per_s(self) -> Optional[float]:
        if self.finish_time is None or self.first_token_time is None:
            return None
        n = len(self.output_tokens)
        dt = self.finish_time - self.first_token_time
        return (n - 1) / dt if n > 1 and dt > 0 else None

    def telemetry(self) -> dict:
        t = {
            "request_id": self.request_id,
            "tier": self.tier,
            "state": self.state,
            "finish_reason": self.finish_reason,
            "prompt_tokens": len(self.prompt),
            "prefix_matched_tokens": self.prefix_matched,
            "output_tokens": len(self.output_tokens),
            "queue_s": self.queue_seconds(),
            "ttft_s": self.ttft_seconds(),
            "decode_tok_s": self.decode_tokens_per_s(),
        }
        if self._spec is not None:
            t["spec_proposed"] = self._spec.proposed
            t["spec_accepted"] = self._spec.accepted
            t["spec_acceptance"] = self._spec.acceptance
        return t


class Scheduler:
    """Owns request state transitions + slot/block accounting. The engine
    drives it: admit() between decode steps, next_prefill() for chunked
    prefill work, start_running()/finish() on transitions."""

    def __init__(self, allocator, max_slots: int, max_model_len: int,
                 window_rings=()):
        self.allocator = allocator
        # the window cache groups (blocks.WindowRings), one per window: a
        # request is admitted when EVERY group can take its need, the full
        # group its worst case and a window group one ring
        self.window_rings = list(window_rings)
        self.max_slots = int(max_slots)
        self.max_model_len = int(max_model_len)
        self.waiting: Deque[Request] = deque()
        self.prefilling: List[Request] = []
        self.running: Dict[int, Request] = {}   # slot -> request
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._reserved_blocks = 0

    # -- intake -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) + 1 > self.max_model_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens leaves no room under "
                f"max_model_len={self.max_model_len}")
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        self.waiting.append(req)
        self._publish()

    def _worst_case_blocks(self, req: Request) -> int:
        total = min(len(req.prompt) + req.max_new_tokens, self.max_model_len)
        return self.allocator.blocks_for(total)

    # -- per-tick transitions ---------------------------------------------
    def admit(self) -> List[Request]:
        """Move waiting requests into prefill while a slot AND a worst-case
        KV reservation fit (FCFS — no request starves). The gate is on the
        SUFFIX worst case: blocks whose prefix already sits in the cache
        cost nothing, which raises effective capacity under shared-prefix
        load."""
        admitted = []
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            total = min(len(req.prompt) + req.max_new_tokens,
                        self.max_model_len)
            if not (self.allocator.can_reserve_prefix(req.prompt, total)
                    and all(r.can_reserve() for r in self.window_rings)):
                break
            self.waiting.popleft()
            req.slot = self._free_slots.pop()
            # materialize the whole worst-case reservation as the block
            # table NOW: decode-time appends never allocate, so the engine
            # can upload each sequence's table once and leave it alone.
            # The table's head is any cached shared prefix; the engine
            # prefils only from req.prefill_pos (= matched tokens).
            _, matched, cow_src, new_blocks = self.allocator.reserve_prefix(
                req.request_id, req.prompt, total)
            req.prefix_matched = matched
            req.prefill_pos = matched
            req._cow_src = cow_src
            req._reserved_blocks = new_blocks
            self._reserved_blocks += new_blocks
            for rings in self.window_rings:
                rings.reserve(req.request_id)
            req.state = "prefill"
            req.prefill_start = time.monotonic()
            self.prefilling.append(req)
            admitted.append(req)
            _ADMITTED.inc()
        self._publish()
        return admitted

    def next_prefill(self) -> Optional[Request]:
        """The request that should get this tick's prefill chunk (FCFS;
        one bounded chunk per tick keeps decode latency bounded)."""
        return self.prefilling[0] if self.prefilling else None

    def start_running(self, req: Request) -> None:
        """Prefill done: the prefix is scattered to pages and the program
        that samples the first token is dispatched. `first_token_time` is
        the engine's to stamp, when the token's value is on the host."""
        self.prefilling.remove(req)
        req.state = "running"
        self.running[req.slot] = req
        self._publish()

    def finish(self, req: Request, reason: str) -> None:
        """Evict: free blocks + slot immediately (the next admit() sees
        them), whatever state the request was in."""
        if req.state == "queued":
            # cancel/timeout of a never-admitted request: drop it from the
            # queue, or admit() would later re-admit a finished request
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
        elif req.state == "prefill":
            self.prefilling.remove(req)
        elif req.state == "running":
            self.running.pop(req.slot, None)
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None
        if req.request_id in self.allocator.sequences():
            # a window ring saw every position whose keys were written:
            # the prompt at the end of prefill, then a token a decode step
            cached = 0 if req.state != "running" else \
                len(req.prompt) + max(0, len(req.output_tokens) - 1)
            self.allocator.free(req.request_id)
            for rings in self.window_rings:
                rings.free(req.request_id, cached)
        self._reserved_blocks -= req._reserved_blocks
        req._reserved_blocks = 0
        req._ws_caches = None
        req._cow_src = None
        req.state = "finished"
        req.finish_reason = reason
        req.finish_time = time.monotonic()
        req._done.set()
        req._progress.set()   # wake streaming readers for the final drain
        _FINISHED.inc(reason=reason)
        self._publish()

    # -- introspection ----------------------------------------------------
    def has_work(self) -> bool:
        return bool(self.waiting or self.prefilling or self.running)

    def counts(self) -> dict:
        return {"waiting": len(self.waiting),
                "prefilling": len(self.prefilling),
                "running": len(self.running),
                "free_slots": len(self._free_slots),
                "reserved_blocks": self._reserved_blocks,
                **({"reserved_window_blocks":
                    sum(r.used_blocks for r in self.window_rings)}
                   if self.window_rings else {})}

    def _publish(self):
        _QUEUED.set(len(self.waiting))
        _RUNNING.set(len(self.prefilling) + len(self.running))
