"""Commit verification — THE hot entry point of the framework (analog of
reference types/validation.go:25-265).

Three variants, all funneling every signature in a Commit into one
BatchVerifier call (one TPU kernel launch):

  verify_commit              — full validation: every non-absent signature
                               must verify (commit AND nil votes); tallied
                               power counts only votes for the block.
  verify_commit_light        — only signatures for the committed block are
                               verified; returns as soon as +2/3 is reached.
  verify_commit_light_trusting — light-client skipping verification: looks
                               validators up by address in the *trusted* set
                               and requires `trust_level` (default 1/3) of
                               its total power.

Batch verification engages when there are at least BATCH_VERIFY_THRESHOLD
signatures (reference types/validation.go:12), whatever the validators' key
types: the one verifier takes every row, and sends those of a key type
without a batch kernel (secp256k1 in a mixed set) down its host lane beside
the others' dispatch (crypto/batch.AdaptiveBatchVerifier). Otherwise single
verification. On batch failure the per-signature bitmap pinpoints the
offending signature for the error message.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

from ..crypto import batch as crypto_batch
from ..libs import trace
from .block import BlockID, Commit
from .validator_set import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2


class InvalidCommitError(ValueError):
    pass


class _CommitVerifier:
    """Batch-verifier shim for the verify_commit* funnel: routes the
    collected signatures through the node's VerifyHub when one is
    running (cross-subsystem micro-batching + gossip-duplicate dedup),
    and otherwise through a local AdaptiveBatchVerifier — the
    verdicts are identical, the hub only changes where/when the batch
    launches. `lane` picks the hub scheduler lane: block-sync /
    state-sync / light-client callers submit as "backfill" so bulk
    catch-up ranges never starve live consensus. Rows of any key type:
    the AdaptiveBatchVerifier partitions by scheme, behind the hub too."""

    def __init__(self, lane: str = "live"):
        self._lane = lane
        self._items: list[tuple] = []
        #: where the last verify() went: "hub" or "local"
        self.via = "local"

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, list[bool]]:
        from ..crypto.verify_hub import logger, running_hub

        hub = running_hub()
        if hub is not None:
            try:
                results = hub.verify_many(self._items, lane=self._lane)
                self.via = "hub"
                return all(results) and bool(results), results
            except Exception as e:  # noqa: BLE001 — stall/shutdown races
                # same contract as verify_one: a wedged hub costs
                # latency, never a spurious commit-verification failure
                logger.warning(
                    "hub verify_many failed (%r); verifying %d sigs locally",
                    e,
                    len(self._items),
                )
        bv = crypto_batch.AdaptiveBatchVerifier()
        bv.add_many(self._items)
        return bv.verify()


def _basic_commit_checks(
    vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    if commit.height != height:
        raise InvalidCommitError(f"commit height {commit.height} != {height}")
    if commit.block_id != block_id:
        raise InvalidCommitError("commit is for a different block")
    if len(vals) != commit.size():
        raise InvalidCommitError(
            f"validator set size {len(vals)} != commit size {commit.size()}"
        )


def _should_batch_verify(commit: Commit) -> bool:
    return commit.size() >= BATCH_VERIFY_THRESHOLD


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    *,
    lane: str = "live",
) -> None:
    """Full commit verification (reference types/validation.go:25).
    Raises InvalidCommitError on failure."""
    _basic_commit_checks(vals, block_id, height, commit)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id,
        vals,
        commit,
        voting_power_needed,
        count_all_signatures=True,
        lookup_by_index=True,
        lane=lane,
    )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    *,
    lane: str = "live",
) -> None:
    """Verify only the signatures for the committed block, stopping at +2/3
    (reference types/validation.go:59) — the block-sync/light-client path."""
    _basic_commit_checks(vals, block_id, height, commit)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id,
        vals,
        commit,
        voting_power_needed,
        count_all_signatures=False,
        lookup_by_index=True,
        lane=lane,
    )


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = Fraction(1, 3),
    *,
    lane: str = "live",
) -> None:
    """Light-client skipping verification against a *trusted* validator set
    (reference types/validation.go:94): validators are matched by address
    (the untrusted set may have rotated), and `trust_level` of the trusted
    power must have signed."""
    if trust_level.numerator * 3 < trust_level.denominator or trust_level > 1:
        raise ValueError("trust level must be in [1/3, 1]")
    total = vals.total_voting_power()
    voting_power_needed = total * trust_level.numerator // trust_level.denominator
    _verify(
        chain_id,
        vals,
        commit,
        voting_power_needed,
        count_all_signatures=False,
        lookup_by_index=False,
        lane=lane,
    )


def _verify(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all_signatures: bool,
    lookup_by_index: bool,
    lane: str = "live",
) -> None:
    if commit.is_aggregate():
        _verify_aggregate(
            chain_id, vals, commit, voting_power_needed, lookup_by_index
        )
    elif _should_batch_verify(commit):
        _verify_batch(
            chain_id, vals, commit, voting_power_needed, count_all_signatures,
            lookup_by_index, lane=lane,
        )
    else:
        _verify_single(
            chain_id, vals, commit, voting_power_needed, count_all_signatures,
            lookup_by_index, lane=lane,
        )


def _iter_entries(vals: ValidatorSet, commit: Commit, lookup_by_index: bool):
    """Yield (idx, commit_sig, validator) for signatures that participate.
    Absent sigs never participate; with address lookup (trusting mode),
    unknown validators are skipped and double-signing addresses rejected."""
    seen: set[bytes] = set()
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        if lookup_by_index:
            val = vals.get_by_index(idx)
            if val is None:
                raise InvalidCommitError(f"no validator at index {idx}")
        else:
            _, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if cs.validator_address in seen:
                raise InvalidCommitError("double vote from same address")
            seen.add(cs.validator_address)
        yield idx, cs, val


def _verify_aggregate(
    chain_id, vals, commit, voting_power_needed, lookup_by_index,
) -> None:
    """Aggregate-commit verification: ONE pairing-product check covers
    every non-absent signer (commit AND nil votes — the aggregate is
    indivisible, so light semantics cannot skip nil signatures; the
    tally still counts only block votes). Routed through the
    crypto/verify_hub.verify_aggregate chokepoint (verdict cache +
    device routing + breaker). Accept/reject surface matches the
    per-signature paths: a forged signer, a wrong bitmap flag, or a
    non-BLS key in an included slot all reject."""
    from ..crypto.bls import KEY_TYPE as BLS_KEY_TYPE
    from ..crypto.verify_hub import verify_aggregate

    tallied = 0
    pubs = []
    msgs = []
    sign_bytes = commit.sign_bytes(chain_id)
    seen: set[bytes] = set()
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        if lookup_by_index:
            val = vals.get_by_index(idx)
            if val is None:
                raise InvalidCommitError(f"no validator at index {idx}")
        else:
            # trusting mode: EVERY included signer must resolve in the
            # trusted set — an aggregate cannot be verified minus the
            # signers the light client doesn't know
            _, val = vals.get_by_address(cs.validator_address)
            if val is None:
                raise InvalidCommitError(
                    f"aggregate commit signer at index {idx} unknown to the "
                    "trusted validator set (aggregate cannot be partially "
                    "verified)"
                )
            if cs.validator_address in seen:
                raise InvalidCommitError("double vote from same address")
            seen.add(cs.validator_address)
        if val.pub_key.TYPE != BLS_KEY_TYPE:
            raise InvalidCommitError(
                f"aggregate commit includes non-BLS signer at index {idx}"
            )
        if cs.signature:
            raise InvalidCommitError(
                f"aggregate commit carries a per-validator signature at "
                f"index {idx}"
            )
        pubs.append(val.pub_key)
        msgs.append(sign_bytes(idx))
        if cs.is_commit():
            tallied += val.voting_power
    if tallied <= voting_power_needed:
        raise InvalidCommitError(
            f"insufficient voting power: got {tallied}, need > {voting_power_needed}"
        )
    if not pubs:
        raise InvalidCommitError("no signatures to verify")
    if not verify_aggregate(pubs, msgs, commit.agg_sig):
        raise InvalidCommitError("aggregate signature verification failed")


def _verify_batch(
    chain_id, vals, commit, voting_power_needed, count_all_signatures,
    lookup_by_index, lane="live",
) -> None:
    bv = _CommitVerifier(lane=lane)
    sign_bytes = commit.sign_bytes(chain_id)
    tallied = 0
    added = 0
    entries = []
    for idx, cs, val in _iter_entries(vals, commit, lookup_by_index):
        if not count_all_signatures and not cs.is_commit():
            continue
        bv.add(val.pub_key, sign_bytes(idx), cs.signature)
        added += 1
        entries.append((idx, cs, val))
        if cs.is_commit():
            tallied += val.voting_power
        # early cut-off: beyond +2/3 no further signatures are needed
        if not count_all_signatures and tallied > voting_power_needed:
            break
    if tallied <= voting_power_needed:
        raise InvalidCommitError(
            f"insufficient voting power: got {tallied}, need > {voting_power_needed}"
        )
    if added == 0:
        raise InvalidCommitError("no signatures to verify")
    ok, bitmap = bv.verify()
    if not ok:
        for (idx, _, _), good in zip(entries, bitmap):
            if not good:
                raise InvalidCommitError(f"invalid signature at index {idx}")
        raise InvalidCommitError("batch verification failed")


def verify_commit_range(
    chain_id: str,
    entries: list[tuple[ValidatorSet, BlockID, int, Commit]],
    *,
    lane: str = "backfill",
) -> None:
    """Cross-commit mega-batching (SURVEY.md §5 "long-context" analog):
    verify a RANGE of commits — e.g. a block-sync window — in ONE batch
    verifier call, so hundreds of heights' signatures form a single TPU
    kernel launch instead of one launch per block.

    Each entry is (validator_set, block_id, height, commit), light
    semantics per commit (+2/3 of block signatures, early cut-off). On a
    batch failure, falls back to per-commit verification to pinpoint the
    offender — so the error surface matches verify_commit_light called
    per entry. Raises InvalidCommitError carrying `failed_index` (the
    entry index) on failure."""
    if not entries:
        return
    # made at the first commit that has rows for it: a range stopped by a
    # basic check, or all of aggregate commits, builds none
    bv = None
    added = 0
    templates = 0
    #: rows by key type, for the span: the verifier partitions them itself
    by_scheme: dict[str, int] = {}
    # the whole collect loop is ONE span (basic checks, sign-bytes, tally,
    # add): thousands of signatures a range, never a row each
    with trace.span("validation", "collect", commits=len(entries)) as sp:
        for ei, (vals, block_id, height, commit) in enumerate(entries):
            try:
                _basic_commit_checks(vals, block_id, height, commit)
                if commit.is_aggregate() or not _should_batch_verify(commit):
                    # aggregate commits are one indivisible pairing product
                    # (verdict-cached in the hub); a one-validator commit
                    # verifies singly
                    verify_commit_light(
                        chain_id, vals, block_id, height, commit, lane=lane
                    )
                    continue
                if bv is None:
                    bv = _CommitVerifier(lane=lane)
                voting_power_needed = vals.total_voting_power() * 2 // 3
                sign_bytes = commit.sign_bytes(chain_id)
                tallied = 0
                for idx, cs, val in _iter_entries(vals, commit, lookup_by_index=True):
                    if not cs.is_commit():
                        continue
                    pub_key = val.pub_key
                    bv.add(pub_key, sign_bytes(idx), cs.signature)
                    by_scheme[pub_key.TYPE] = by_scheme.get(pub_key.TYPE, 0) + 1
                    added += 1
                    tallied += val.voting_power
                    if tallied > voting_power_needed:
                        break
                templates += sign_bytes.templates
                if tallied <= voting_power_needed:
                    raise InvalidCommitError(
                        f"insufficient voting power at height {height}: "
                        f"got {tallied}, need > {voting_power_needed}"
                    )
            except InvalidCommitError as e:
                e.failed_index = ei
                raise
        edwards, host = crypto_batch.rows_by_lane(by_scheme)
        sp.set(sigs=added, templates=templates, edwards=edwards, host=host)
    if not added:
        return
    with trace.span("validation", "verify", sigs=added) as sp:
        ok, _bitmap = bv.verify()
        sp.set(via=bv.via)
    if ok:
        return
    # locate the offending commit: per-commit fallback
    with trace.span("validation", "locate", commits=len(entries)):
        for ei, (vals, block_id, height, commit) in enumerate(entries):
            try:
                verify_commit_light(chain_id, vals, block_id, height, commit, lane=lane)
            except InvalidCommitError as e:
                e.failed_index = ei
                raise
    raise InvalidCommitError("range batch failed but all commits verify singly")


def _verify_single(
    chain_id, vals, commit, voting_power_needed, count_all_signatures,
    lookup_by_index, lane="live",
) -> None:
    from ..crypto.verify_hub import verify_one

    sign_bytes = commit.sign_bytes(chain_id)
    tallied = 0
    for idx, cs, val in _iter_entries(vals, commit, lookup_by_index):
        if not count_all_signatures and not cs.is_commit():
            continue
        if not verify_one(
            val.pub_key, sign_bytes(idx), cs.signature,
            lane=lane,
        ):
            raise InvalidCommitError(f"invalid signature at index {idx}")
        if cs.is_commit():
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            return
    if tallied <= voting_power_needed:
        raise InvalidCommitError(
            f"insufficient voting power: got {tallied}, need > {voting_power_needed}"
        )
