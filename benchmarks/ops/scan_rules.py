"""Op kind `scan_rules`: ops/scan.py's scan (same draw, same send) on a
table whose `user_specified_compaction` rules an operator may have
triggered (ops/compact.py). Held to the rules' guarantees with
benchmarks/reference_rules.py over the reference's rows:

  until the first trigger was acknowledged, every page equals the
  reference exactly (G2: a matched row is still there; G1);
  from then on each page (one ScanResponse) equals the first rows from
  its own start key of the reference either WITH the partition's
  delete-matched rows or WITHOUT them: never a mixture, never a
  changed value, never a missing unmatched row (G1, G2);
  a page that came without them latches its partition: no later page
  of it may hold one (G3). The replay goes operation by operation,
  time went scan_multi call by call: a page's time is (the send call,
  its place among the op's pages = the round of that call);
  the op returns its n rows unless the partition ends.

args as ops/scan.py; reply = Pages([ScanResponse]).
"""

import bisect
import itertools

from benchmarks.ops import scan
from benchmarks.ops.scan import draw, readback  # noqa: F401

_CALLS = itertools.count(1)
_NEVER = (float("inf"), 0)
# the live rows of the newest replayed table that a delete rule
# matches, counted when the replay meets the first trigger (G4 holds
# every replica to dropping each once: readers/compact_window.py)
MATCHED_LIVE = None


class Pages(list):
    """An op's pages and the send call that fetched them."""

    call = 0


def send(client, batch, ctx):
    call = next(_CALLS)
    out = []
    for reply, took in scan.send(client, batch, ctx):
        if reply is not None:
            reply = Pages(reply)
            reply.call = call
        out.append((reply, took))
    return out


def rules_state(model) -> dict:
    """What the replay knows of the rules, kept on the reference:
    the parsed ruleset, whether a trigger was acknowledged, the
    partitions latched, and each row key's verdict."""
    state = getattr(model, "rules_state", None)
    if state is None:
        # first_without / last_with: per partition, the earliest page
        # that came without the matched rows though some lay in its
        # range, and the latest that held one
        state = model.rules_state = {"rules": None, "triggered": False,
                                     "first_without": {}, "last_with": {},
                                     "deletes": {}}
    return state


def count_matched_live(model) -> int:
    """The rows without a TTL (no other is live in a run: the expired
    ones went in set-up) that a delete rule matches."""
    global MATCHED_LIVE
    state = rules_state(model)
    MATCHED_LIVE = sum(1 for rows in model.rows
                       for key, (_value, ets) in rows.items()
                       if ets == 0 and _deletes(state, key))
    return MATCHED_LIVE


def _deletes(state, key: bytes) -> bool:
    verdict = state["deletes"].get(key)
    if verdict is None:
        hk_len = int.from_bytes(key[:2], "big")
        verdict = state["deletes"][key] = state["rules"].deletes(
            key[2:2 + hk_len], key[2 + hk_len:])
    return verdict


def _both(model, state, pidx, start, inclusive, k, now):
    """The first k + 1 unexpired rows of the partition from `start`
    (fewer where it ends), with and without the delete-matched rows:
    ([(key, value)], [...])."""
    order = model._sorted(pidx)
    rows = model.rows[pidx]
    i = (bisect.bisect_left if inclusive else bisect.bisect_right)(
        order, start)
    with_, without = [], []
    while i < len(order) and len(without) < k + 1:
        key = order[i]
        value, ets = rows[key]
        if not model.expired(ets, now):
            if len(with_) < k + 1:
                with_.append((key, value))
            if not _deletes(state, key):
                without.append((key, value))
        i += 1
    return with_, without


def check(model, args, reply, now):
    pidx, start, n, _req = args
    state = rules_state(model)
    pages = [[(kv.key, kv.value) for kv in resp.kvs] for resp in reply]
    if not state["triggered"]:
        got = [row for page in pages for row in page]
        want = model.scan(pidx, start, n, now)
        if got != want:
            return (f"scan of partition {pidx} from {start!r} for {n} "
                    f"before any compaction returned {len(got)} rows, the "
                    f"reference {len(want)}")
        return None
    at, inclusive, remaining = start, True, n
    call = getattr(reply, "call", 0)
    first_without, last_with = state["first_without"], state["last_with"]
    for j, page in enumerate(pages):
        k = len(page)
        with_, without = _both(model, state, pidx, at, inclusive, k, now)
        # a page shorter than asked for is the server's bound on the
        # rows one read examines, and is continued; an empty one says
        # the partition has ended
        fits_without = page == without[:k] and (k > 0 or not without)
        fits_with = page == with_[:k] and (k > 0 or not with_)
        if not (fits_without or fits_with):
            return (f"page of {k} rows of partition {pidx} from {at!r} "
                    f"equals the reference neither with nor without the "
                    f"rows the delete rules match")
        if not (fits_without and fits_with):    # matched rows in range
            when = (call, j)
            if fits_without:
                first_without[pidx] = min(first_without.get(pidx, _NEVER),
                                          when)
            else:
                last_with[pidx] = max(last_with.get(pidx, (0, 0)), when)
            if last_with.get(pidx, (0, 0)) > first_without.get(pidx, _NEVER):
                return (f"a page of partition {pidx} holds rows the delete "
                        f"rules match (call, round {last_with[pidx]}) after "
                        f"one had come without them "
                        f"({first_without[pidx]})")
        remaining -= k
        if k:
            at, inclusive = page[-1][0], False
    if remaining > 0 and (not pages or pages[-1]):
        return (f"scan of partition {pidx} from {start!r} stopped "
                f"{remaining} rows short of {n} before the partition ended")
    if remaining < 0:
        return f"scan of partition {pidx} returned {-remaining} rows too many"
    return None


def apply(model, args):
    pass
