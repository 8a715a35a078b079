"""Canonical registry of query error codes (QueryException parity).

Reference: org.apache.pinot.common.exception.QueryException assigns every
failure surface a stable numeric code that travels in BrokerResponse
`exceptions: [{"errorCode", "message"}]` entries so clients can react
without string-matching. This module is the single place those numbers
live; everything else imports `QueryErrorCode` (an IntEnum, so members
serialize as plain ints in JSON and compare equal to raw wire values).

pinotlint's `error-code-registry` checker flags any registered numeric
literal used in an error-code position outside this module, so new call
sites cannot re-hardcode 250/503/... and drift from the registry.
"""

from __future__ import annotations

import enum


class QueryErrorCode(enum.IntEnum):
    """Numeric query error codes (QueryException.*_ERROR_CODE parity)."""

    #: generic server-side execution failure; the default code attached to
    #: partial-result exception entries when nothing more specific is known
    QUERY_EXECUTION = 200

    #: query exceeded its deadline (EXECUTION_TIMEOUT_ERROR_CODE)
    EXECUTION_TIMEOUT = 250

    #: query was cancelled via DELETE /query/{id} (QueryCancelledException)
    QUERY_CANCELLATION = 503

    #: admission tier shed the query before any work was enqueued — queue
    #: overflow, scheduler shutdown, or projected completion past the deadline
    #: (SERVER_OUT_OF_CAPACITY_ERROR_CODE parity); travels as HTTP 503
    SERVER_OUT_OF_CAPACITY = 211

    #: per-table / per-tenant QPS quota rejection by QueryQuotaManager
    #: (TOO_MANY_REQUESTS_ERROR_CODE parity); travels as HTTP 429
    QUOTA_EXCEEDED = 429

    #: a segment's on-disk bytes failed integrity verification (whole-file
    #: or per-entry CRC mismatch, torn/truncated file) and every recovery
    #: source — local copy, deep store, peer replicas — is also bad
    #: (SEGMENT_MISSING/data-corruption parity). Rides in a 200
    #: BrokerResponse as a partial-result exception entry.
    SEGMENT_CORRUPTED = 260

    #: no controller candidate is reachable and leading — every configured
    #: URL refused/timed out or answered "not leader" without a followable
    #: leaderUrl hint (BROKER_INSTANCE_MISSING / controller-unreachable
    #: parity). Travels as HTTP 503 so clients back off and retry.
    CONTROLLER_UNAVAILABLE = 270

    #: a segment upload failed before any cluster metadata referenced it
    #: (ENOSPC, short write, bytes failing CRC); the deep store holds no
    #: partial dir. Typed so upload clients can distinguish "retry the
    #: upload" from generic execution failures.
    SEGMENT_UPLOAD = 290

    #: wire datatable (de)serialization failure between query hops
    #: (DATA_TABLE_SERIALIZATION_ERROR parity) — corrupt frame, unknown
    #: column type, or a value the encoder cannot represent
    DATA_TABLE_SERIALIZATION = 550


#: Error codes that map to a non-200 HTTP status at response boundaries.
#: Everything else stays the BrokerResponse convention: HTTP 200 with the
#: code inside `exceptions[]`. Shed/quota responses use real statuses so
#: load balancers and clients can back off without parsing the body.
_HTTP_STATUS_BY_CODE = {
    int(QueryErrorCode.SERVER_OUT_OF_CAPACITY): 503,
    int(QueryErrorCode.QUOTA_EXCEEDED): 429,
    int(QueryErrorCode.CONTROLLER_UNAVAILABLE): 503,
}


class SegmentCorruptedError(ValueError):
    """A segment failed CRC/structural verification. Subclasses ValueError
    (corrupt bytes are malformed values) so legacy callers that guard
    segment decode with `except ValueError` keep working; carries
    `error_code` so `code_of` maps it to `SEGMENT_CORRUPTED` at every
    response boundary and `path` names the bad copy for quarantine
    runbooks."""

    error_code = QueryErrorCode.SEGMENT_CORRUPTED

    def __init__(self, message: str, path: str | None = None):
        super().__init__(message)
        self.path = path


class ControllerUnavailableError(ConnectionError):
    """Every configured controller candidate is down or refusing leadership
    (connection failures and 503s with no followable leaderUrl across the
    bounded retry budget). Subclasses ConnectionError so legacy callers that
    guard discovery with `except ConnectionError`/`except OSError` keep
    working; carries `error_code` so response boundaries surface a typed
    503 with Retry-After instead of an untyped stack."""

    error_code = QueryErrorCode.CONTROLLER_UNAVAILABLE

    def __init__(self, message: str, candidates: list[str] | None = None, retry_after_s: float = 1.0):
        super().__init__(message)
        self.candidates = list(candidates or [])
        self.retry_after_s = retry_after_s


class ServerTimedOut(RuntimeError):
    """A server took a call and did not answer inside the call's time: unlike
    one that refuses the connection, it may still be doing what it was asked
    (a state transition is a segment's load)."""


class SegmentUploadError(OSError):
    """A segment upload failed before any cluster metadata referenced it
    (ENOSPC, crash, or the written bytes failing verification). The errno
    of the underlying OSError is preserved — `e.errno == errno.ENOSPC`
    is the disk-full contract — and the controller guarantees the deep
    store holds no partial segment dir when this is raised. Carries
    `error_code` so the controller HTTP boundary returns a typed failure
    instead of an anonymous 500."""

    error_code = QueryErrorCode.SEGMENT_UPLOAD


def code_of(exc: BaseException, default: int = QueryErrorCode.QUERY_EXECUTION) -> int:
    """Error code carried by an exception (its `error_code` attribute), or
    `default`. The one sanctioned way to map an arbitrary exception to a
    wire code at response boundaries."""
    return int(getattr(exc, "error_code", default))


def http_status_of(exc: BaseException) -> int | None:
    """HTTP status override for admission-tier rejections (503 shed /
    429 quota), or None for errors that ride in a 200 BrokerResponse."""
    return _HTTP_STATUS_BY_CODE.get(code_of(exc, default=0))


def retry_after_of(exc: BaseException, default: float = 1.0) -> float:
    """`Retry-After` seconds carried by an admission rejection (its
    `retry_after_s` attribute), floored at 1 s for header sanity."""
    v = getattr(exc, "retry_after_s", None)
    try:
        return max(1.0, float(v)) if v is not None else float(default)
    except (TypeError, ValueError):
        return float(default)
