"""Light client attack detection (reference: light/detector.go:424).

After a skipping verification the client holds a trace of verified light
blocks primary-side. The detector replays the target height against every
witness; a witness serving a conflicting header triggers divergence
examination: walk the primary trace to find the common (last agreed)
block, verify the witness's conflicting block from there, and — if the
witness proves a validly-signed conflicting header — build
LightClientAttackEvidence against the primary chain and report it to the
other providers.
"""

from __future__ import annotations

from ..types.evidence import LightClientAttackEvidence
from ..types.light_block import LightBlock
from . import verifier
from .errors import (
    ConflictingHeadersError,
    LightBlockNotFoundError,
    LightClientError,
)


def detect_divergence(client, now_ns: int | None = None) -> list:
    """Cross-check client.latest_trace's target against all witnesses
    (detector.go:48-142). Returns the evidence built (possibly empty);
    raises ConflictingHeadersError after reporting when an attack is
    proven, mirroring the reference's halt signal.
    """
    now_ns = client._now(now_ns)
    trace = client.latest_trace
    if len(trace) < 2 or not client.witnesses:
        return []
    target = trace[-1]
    evidence: list[LightClientAttackEvidence] = []
    bad_witnesses: list[int] = []
    for i, witness in enumerate(client.witnesses):
        try:
            alt = witness.light_block(target.height)
        except LightBlockNotFoundError:
            continue
        except Exception:
            bad_witnesses.append(i)
            continue
        if alt.hash() == target.hash():
            continue
        try:
            ev = examine_conflicting_header_against_trace(
                trace, alt, witness, now_ns, client
            )
        except LightClientError:
            # witness can't even agree with the root of trust: faulty
            # witness, drop it and keep scanning the others
            bad_witnesses.append(i)
            continue
        if ev is not None:
            evidence.append(ev)
            # report against the primary to every witness + the primary
            witness.report_evidence(ev)
            client.primary.report_evidence(ev)
    if bad_witnesses:
        client.remove_witnesses(bad_witnesses)
    if evidence:
        raise ConflictingHeadersError(evidence[0].conflicting_block)
    return evidence


def examine_conflicting_header_against_trace(
    trace: list[LightBlock],
    divergent: LightBlock,
    source,
    now_ns: int,
    client,
) -> LightClientAttackEvidence | None:
    """detector.go:288-422: find the common block in the trace, then verify
    the divergent header from it using the witness as source. If it
    verifies, the PRIMARY equivocated: evidence targets the primary's
    block; the caller reports it."""
    common = None
    for lb in trace:
        try:
            alt = source.light_block(lb.height)
        except Exception:
            return None
        if alt.hash() == lb.hash():
            common = lb
        else:
            break
    if common is None:
        raise LightClientError(
            "witness disagrees with the root of trust itself"
        )
    # Verify the divergent block from the common checkpoint via the
    # witness's chain of headers (skipping verification).
    try:
        if divergent.height != common.height + 1:
            verifier.verify_non_adjacent(
                common.signed_header,
                common.validator_set,
                divergent.signed_header,
                divergent.validator_set,
                client.trust_options.period_ns,
                now_ns,
                client.max_clock_drift_ns,
                client.trust_level,
            )
        else:
            verifier.verify_adjacent(
                common.signed_header,
                divergent.signed_header,
                divergent.validator_set,
                client.trust_options.period_ns,
                now_ns,
                client.max_clock_drift_ns,
            )
    except Exception:
        # witness could not prove its header: witness is faulty, not the
        # primary — no evidence against the primary
        return None
    # Both chains verified from the common block: the primary's trace block
    # at the divergent height is the attack header from the witness's view;
    # evidence carries the PRIMARY's conflicting block.
    primary_block = trace[-1]
    byzantine = _byzantine_validators(common, primary_block, divergent)
    return LightClientAttackEvidence(
        conflicting_block=primary_block,
        common_height=common.height,
        byzantine_validators=byzantine,
        total_voting_power=common.validator_set.total_voting_power(),
        timestamp_ns=common.time_ns,
    )


def _byzantine_validators(common, primary_block, divergent) -> list:
    """Validators from the common set that signed the primary's conflicting
    commit (types/evidence.go GetByzantineValidators, equivocation case)."""
    out = []
    commit = primary_block.signed_header.commit
    from ..types.block import BLOCK_ID_FLAG_COMMIT

    vals = common.validator_set
    index = vals.address_index()  # one map for the whole commit
    for sig in commit.signatures:
        if sig.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        idx = index.get(sig.validator_address, -1)
        if idx >= 0:
            out.append(vals.validators[idx])
    return out
