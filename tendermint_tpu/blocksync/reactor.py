"""Blocksync reactor (reference internal/blocksync/v0/reactor.go:78,
channel 0x40) — restructured as the TPU pipeline:

  fetch (network, BlockPool) → sign-bytes construction (host) →
  RANGE-batched commit verification (one TPU call per window,
  verify_commit_range) → ApplyBlock (ABCI)

The reference verifies and applies one block per poolRoutine tick
(reactor.go:439-568); here a contiguous window of up to `window` blocks
is verified in batched calls, then applied in order. A run is PLANNED
before it is verified: after height h the state holds the validator sets
of h+1 and h+2, and every fetched header names its own set
(`validators_hash`), so each commit is verified against the set its
header names — as many blocks as name one of those two sets in ONE call
— and the run is cut where a header names a third; the rest is planned
again once the apply has derived the next sets. No commit is ever
verified against a set the state did not derive, and none twice."""

from __future__ import annotations

import asyncio
import logging

from ..libs import trace
from ..libs.clock import SYSTEM, Clock
from ..libs.service import Service
from ..p2p.peermanager import PeerStatus
from ..p2p.router import Channel
from ..p2p.types import Envelope, PeerError
from ..state.execution import BlockExecutor
from ..types.block import BlockID
from ..types.validation import InvalidCommitError, verify_commit_light, verify_commit_range
from . import BLOCKSYNC_CHANNEL
from . import messages as m
from .pool import BlockPool

STATUS_INTERVAL = 2.0
REQUEST_INTERVAL = 0.02
SWITCH_CHECK_INTERVAL = 0.2
DEFAULT_WINDOW = 64


class BlockSyncReactor(Service):
    def __init__(
        self,
        state,
        block_exec: BlockExecutor,
        block_store,
        channel: Channel,
        peer_updates: asyncio.Queue,
        *,
        window: int = DEFAULT_WINDOW,
        active: bool = True,
        clock: Clock | None = None,
        logger: logging.Logger | None = None,
    ):
        super().__init__("bs-reactor", logger)
        # duration domain (range-verify latency, pool RTO/ban clocks);
        # injected so chaos clock drift reaches sync bookkeeping too
        self.clock = clock or SYSTEM
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.channel = channel
        self.peer_updates = peer_updates
        self.window = window
        # active=False serves blocks/status to peers but never fetches or
        # applies (a validator started without block-sync must not race
        # live consensus for the same heights)
        self.active = active
        self.pool = BlockPool(state.last_block_height + 1, clock=self.clock)
        self.synced = asyncio.Event()  # set on caught-up (switch to consensus)
        self.metrics = {
            "blocks_applied": 0,
            "sigs_verified": 0,
            "ranges": 0,
            # one plan a verify call; a cut is a plan that ended at a header
            # naming a set the state does not know yet (a third set);
            # sequential_blocks counts blocks applied after a one-commit
            # verify in _apply_sequential (0 on honest traffic)
            "plans": 0,
            "cuts": 0,
            "sequential_blocks": 0,
            "peer_bans": 0,
        }
        # height -> hash of the validator set under which the commit FOR
        # that height was signature-proven (a planned range call, or the
        # sequential fallback); lets apply_block skip the redundant host
        # re-verification of each block's LastCommit. One entry a height,
        # because one call proves commits under up to two sets. NOTE what is
        # NOT in it: a range starting at height h proves the commits FOR
        # h..upto (block h+1's LastCommit is the commit for h) — nothing
        # about the commit for h-1, so the first block applied after
        # startup/resume is full-verified (commit_verified=False). An entry
        # is dropped once its block is applied, on redo() (a re-fetched block
        # can carry a different commit) and on resume() (stale after a
        # consensus interlude).
        self._commit_proofs: dict[int, bytes] = {}

    async def on_start(self) -> None:
        self.spawn(self._process_peer_updates(), name="bsr.peers")
        self.spawn(self._process_inbound(), name="bsr.in")
        self.spawn(self._status_routine(), name="bsr.status")
        if self.active:
            self.spawn(self._request_routine(), name="bsr.req")
            self.spawn(self._sync_routine(), name="bsr.sync")
        else:
            self.synced.set()

    def resume(self, state) -> None:
        """Re-activate the fetch/verify/apply pipeline after consensus
        fell too far behind (the reference 0.37 'switch back to
        block-sync'). Caller must have paused consensus first."""
        self.state = state
        self.pool.height = state.last_block_height + 1
        self.pool.blocks = {
            h: b for h, b in self.pool.blocks.items() if h > state.last_block_height
        }
        self._commit_proofs.clear()
        self.synced = asyncio.Event()
        self.spawn(self._request_routine(), name="bsr.req")
        self.spawn(self._sync_routine(), name="bsr.sync")

    # -- peers -----------------------------------------------------------

    async def _process_peer_updates(self) -> None:
        while True:
            upd = await self.peer_updates.get()
            if upd.status == PeerStatus.UP:
                self._send(m.StatusRequest(), to=upd.node_id)
                # advertise our own range so the peer can sync from us
                self._send(
                    m.StatusResponse(self.block_store.height(), self.block_store.base()),
                    to=upd.node_id,
                )
            else:
                self.pool.remove_peer(upd.node_id)

    def _send(self, msg, *, to: str = "", broadcast: bool = False) -> None:
        try:
            self.channel.out_q.put_nowait(
                Envelope(BLOCKSYNC_CHANNEL, msg, to=to, broadcast=broadcast)
            )
        except asyncio.QueueFull:
            self.logger.warning("blocksync outbound queue full")

    # -- inbound ---------------------------------------------------------

    async def _process_inbound(self) -> None:
        async for env in self.channel:
            msg = env.message
            if isinstance(msg, m.StatusRequest):
                self._send(
                    m.StatusResponse(self.block_store.height(), self.block_store.base()),
                    to=env.from_,
                )
            elif isinstance(msg, m.StatusResponse):
                self.pool.set_peer_range(env.from_, msg.base, msg.height)
            elif isinstance(msg, m.BlockRequest):
                block = self.block_store.load_block(msg.height)
                if block is not None:
                    self._send(m.BlockResponse(block), to=env.from_)
                else:
                    self._send(m.NoBlockResponse(msg.height), to=env.from_)
            elif isinstance(msg, m.BlockResponse):
                self.pool.add_block(env.from_, msg.block)
            elif isinstance(msg, m.NoBlockResponse):
                self.pool.no_block(env.from_, msg.height)

    # -- outbound request/status loops ----------------------------------

    async def _request_routine(self) -> None:
        while not self.synced.is_set():
            for height, peer_id in self.pool.next_requests():
                self._send(m.BlockRequest(height), to=peer_id)
            # peers the pool banned for repeated consecutive timeouts are
            # evicted for real (fatal PeerError -> router disconnect) AND
            # promoted into the peer manager's dial quarantine (ban=True:
            # escalating cooldown, no redial) — the pool-local timed ban
            # alone let a bad peer bounce back every BAN_COOLDOWN
            for pid in self.pool.take_banned():
                self.metrics["peer_bans"] += 1
                await self.channel.error(
                    PeerError(pid, "blocksync: repeated request timeouts", ban=True)
                )
            await asyncio.sleep(REQUEST_INTERVAL)

    async def _status_routine(self) -> None:
        while True:
            self._send(m.StatusRequest(), broadcast=True)
            await asyncio.sleep(STATUS_INTERVAL)

    # -- the pipeline ----------------------------------------------------

    async def _sync_routine(self) -> None:
        """fetch → verify (range-batched) → apply (reference poolRoutine
        reactor.go:439, restructured)."""
        while not self.synced.is_set():
            run = self.pool.peek_range(self.window + 1)
            if len(run) < 2:
                if self.pool.is_caught_up():
                    # hand over to consensus (reference SwitchToConsensus);
                    # we keep serving BlockRequests/status to other peers
                    self.synced.set()
                    return
                # fewer than two blocks in hand: waiting on the fetch
                with trace.span("blocksync", "idle", clock=self.clock):
                    await asyncio.sleep(SWITCH_CHECK_INTERVAL)
                continue
            # one range is one trace: build, verify (on a worker thread:
            # to_thread carries the context, so the commit funnel's and
            # the hub's spans join it) and one apply per block
            with trace.span(
                "blocksync", "range", root=True, clock=self.clock,
                first=run[0][0].header.height, n=len(run) - 1,
            ) as sp:
                await self._verify_and_apply(run, sp)

    async def _verify_and_apply(self, run, range_span=trace.NOP_SPAN) -> None:
        """Verify blocks run[0..-2] using each successor's LastCommit, as
        many as the state knows the validator set of in ONE batched call,
        and apply them in order; plan the rest of the run again from the
        state the apply produced. A static set gives one plan of the whole
        run."""
        chain_id = self.state.chain_id
        n = len(run) - 1
        # Stage 1 (host), once a run: part sets and block IDs. Block i is
        # verified by run[i+1].last_commit.
        parts_list = []
        block_ids = []
        with trace.span("blocksync", "build", n=n):
            for i in range(n):
                block = run[i][0]
                parts = block.make_part_set()
                parts_list.append(parts)
                block_ids.append(BlockID(block.hash(), parts.header))
        first_height = run[0][0].header.height

        start = 0
        run_sigs = 0
        while start < n:
            # Stage 2 (host): plan. After height h the state holds the sets
            # of h+1 (validators) and h+2 (next_validators); a header names
            # its own. An entry takes the set its header names; the plan
            # ends at the first header that names neither (state.validate
            # holds every header to the true state at apply time, so a
            # header that names the wrong one of the two is refused there).
            vals, nxt = self.state.validators, self.state.next_validators
            known = {nxt.hash(): nxt, vals.hash(): vals}
            entries = []
            sets = set()
            with trace.span("blocksync", "plan", run=n - start) as plan_span:
                for i in range(start, n):
                    block = run[i][0]
                    named = block.header.validators_hash
                    entry_vals = known.get(named)
                    if entry_vals is None:
                        break
                    sets.add(named)
                    entries.append(
                        (entry_vals, block_ids[i], block.header.height,
                         run[i + 1][0].last_commit)
                    )
                cut = "run_end" if start + len(entries) == n else "third_set"
                plan_span.set(planned=len(entries), sets=len(sets), cut=cut)
            self.metrics["plans"] += 1
            self.metrics["cuts"] += cut == "third_set"
            if not entries:
                # the NEXT block names a set the state contradicts: no commit
                # can vouch for it; state.validate refuses it by name
                await self._apply_sequential(run, parts_list, block_ids, start, start + 1)
                return
            stop = start + len(entries)

            # Stage 3 (TPU): one batched verification for the planned blocks
            try:
                n_sigs = sum(
                    sum(1 for s in e[3].signatures if s.is_commit()) for e in entries
                )
                run_sigs += n_sigs
                range_span.set(sigs=run_sigs)
                with trace.span("blocksync", "verify", sigs=n_sigs, sets=len(sets)):
                    await asyncio.to_thread(
                        verify_commit_range, chain_id, entries, lane="backfill"
                    )
                self.metrics["ranges"] += 1
                self.metrics["sigs_verified"] += n_sigs
                # the batch proved the commit FOR each planned height (its
                # successor's LastCommit), each under the set its header names
                for entry_vals, _bid, height, _commit in entries:
                    self._commit_proofs[height] = entry_vals.hash()
            except InvalidCommitError as e:
                # every entry was held to the set its own header names and
                # the state derived, so this is no rotation: the commit at
                # failed_index is bad, or a header lies about its set. Blocks
                # before it are good; go through the planned blocks one
                # commit at a time against the true (evolving) state, which
                # applies those and punishes the pair that served the bad one.
                self.logger.debug(
                    "range verify failed at h=%d (%s); falling back to sequential",
                    first_height + start + getattr(e, "failed_index", 0),
                    e,
                )
                await self._apply_sequential(run, parts_list, block_ids, start, stop)
                return

            # Stage 4: apply in order (ABCI)
            for i in range(start, stop):
                block, provider = run[i]
                if not await self._apply_one(
                    block, block_ids[i], parts_list[i], run[i + 1][0], provider
                ):
                    return
            start = stop

    async def _apply_sequential(self, run, parts_list, block_ids, start, stop) -> None:
        """Per-block verify (against the true evolving validator set) +
        apply of run[start:stop] — the answer to a planned call that FAILED
        against its true sets, and the semantic twin of the reference's
        one-at-a-time poolRoutine: blocks before the bad commit are applied,
        the pair that served it is punished."""
        chain_id = self.state.chain_id
        with trace.span("blocksync", "sequential", n=stop - start) as sp:
            applied = 0
            for i in range(start, stop):
                block, provider = run[i]
                height = block.header.height
                next_block, next_provider = run[i + 1]
                try:
                    await asyncio.to_thread(
                        verify_commit_light,
                        chain_id,
                        self.state.validators,
                        block_ids[i],
                        height,
                        next_block.last_commit,
                        lane="backfill",
                    )
                except InvalidCommitError as e:
                    await self._punish(height, provider, next_provider, e)
                    break
                # commit for `height` proven against the TRUE set for that
                # height (state.validators now == state.last_validators when
                # block height+1 is applied next iteration)
                self._commit_proofs[height] = self.state.validators.hash()
                if not await self._apply_one(
                    block, block_ids[i], parts_list[i], next_block, provider
                ):
                    break
                applied += 1
                self.metrics["sequential_blocks"] += 1
            sp.set(applied=applied)

    async def _punish(self, height, provider, next_provider, err) -> None:
        """Bad block/commit confirmed against the correct validator set:
        both the block provider and the commit provider are suspect
        (reference reactor.go:556-568)."""
        self.logger.info(
            "invalid commit at height %d from %s: %s", height, provider[:12], err
        )
        await self.channel.error(PeerError(provider, f"bad block: {err}"))
        if next_provider != provider:
            await self.channel.error(PeerError(next_provider, f"bad commit: {err}"))
        self.pool.redo(height, provider, next_provider)
        self._drop_commit_proofs(height)

    def _drop_commit_proofs(self, height: int) -> None:
        """Forget the proofs of the commits FOR `height` and above: their
        blocks are fetched again and may carry other commits."""
        for h in [h for h in self._commit_proofs if h >= height]:
            del self._commit_proofs[h]

    def _commit_preverified(self, height: int) -> bool:
        """True when block `height`'s LastCommit (the commit for
        height-1) was already signature-proven by a planned range call or
        the sequential fallback against exactly the set validate_block will
        check it with (state.last_validators).

        A range proves commits from its OWN first height onward, never the
        commit for first_height-1, so the first block applied after
        startup/resume always takes the full apply-time verification path
        (commit_verified=False)."""
        proven = self._commit_proofs.get(height - 1)
        return proven is not None and proven == self.state.last_validators.hash()

    async def _apply_one(self, block, block_id, parts, next_block, provider) -> bool:
        height = block.header.height
        try:
            with trace.span("blocksync", "apply", height=height):
                if self.block_store.height() < height:
                    with trace.span("blocksync", "save_block"):
                        self.block_store.save_block(
                            block, parts, next_block.last_commit
                        )
                self.state, _ = await self.block_exec.apply_block(
                    self.state,
                    block_id,
                    block,
                    commit_verified=self._commit_preverified(height),
                )
            self.metrics["blocks_applied"] += 1
        except Exception as e:
            self.logger.error("apply failed at height %d: %r", height, e)
            await self.channel.error(PeerError(provider, f"apply: {e!r}"))
            self.pool.redo(height, provider)
            self._drop_commit_proofs(height)
            return False
        self._commit_proofs.pop(height - 1, None)
        self.pool.pop(height)
        return True
