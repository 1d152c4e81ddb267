"""Reading the program's answer to a light verification, and putting the
reference (or a control) to the same question."""

from __future__ import annotations

import re

from cometbft_tpu.light.errors import InvalidHeaderError, LightClientError
from cometbft_tpu.types.validation import (
    NotEnoughVotingPowerError, VerificationError,
)

from ..reference import light_ref

_LANE = re.compile(r"wrong signature \(#(\d+)\)")


def of_exception(exc: BaseException):
    """("reject", lane) when the program refused a commit naming a lane,
    ("power", None) for too little power, else ("error", text): anything
    else is not an answer and counts as a failed request."""
    seen = set()
    e = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, NotEnoughVotingPowerError):
            return ("power", None)
        if isinstance(e, VerificationError):
            m = _LANE.search(str(e))
            if m:
                return ("reject", int(m.group(1)))
        e = getattr(e, "reason", None) or e.__cause__
    kind = "light" if isinstance(exc, (LightClientError, InvalidHeaderError)) \
        else "other"
    return ("error", f"{kind}: {type(exc).__name__}: {exc}"[:200])


# --- controls: the reference in the program's place with one guarantee
# broken ("every counted signature verifies"), the steps that would tempt a
# later PR. Each takes the lanes and returns one verdict per lane.


def _stride8(lanes):
    """Spot check: verify every 8th lane, take the rest on trust."""
    from ..reference import ed25519_oracle as oracle

    bits = [True] * len(lanes)
    bits[::8] = oracle.verify_lanes(lanes[::8])
    return bits


def _trust_all(lanes):
    """Count the power, verify nothing."""
    return [True] * len(lanes)


CONTROLS = {"stride8": _stride8, "trust_all": _trust_all}


def reference_job(job):
    """Worker: (commit, pubkeys, power, control) -> verdict. Top level so
    that a spawned process can run it."""
    commit, pubkeys, power, control = job
    fn = CONTROLS[control] if control else None
    return light_ref.verify_commit_light(commit, pubkeys, power, fn)
