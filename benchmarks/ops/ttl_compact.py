"""Op kind `ttl_compact`: the operator's manual compaction of a
retention table with no rules, as pegasus_manual_compact.sh runs it
for a table whose `default_ttl` has left expired rows on disk: the
trigger of ops/compact.py (one admin `update_app_envs` to the meta
carrying the mix's `app_envs` and `manual_compact.once.trigger_time`
= now), whose envs here hold no `user_specified_compaction`. Every
pass is the TTL filter alone: the default-TTL rewrite, expiry, the
stale-split drop.

The reference needs nothing of it: reads already hide every row a
pass drops. At the first acknowledged trigger it counts the rows the
reference holds expired, the rows on disk every replica's passes drop
once between them (the config's T3), and logs them: the per-layer
metric ttl_compact_dropped_rows reads the replicas' count once the
pool has drained (readers/compact_window.py).

args = (table, {env: text}); reply = True.
"""

import sys

from benchmarks.ops.compact import check, draw, readback, send  # noqa: F401
from benchmarks.reference import epoch_now

# the newest run's count: the reference's rows expired at its first
# acknowledged trigger
EXPIRED_ON_DISK = None


def count_expired(model, now: int) -> int:
    return sum(1 for rows in model.rows for _value, ets in rows.values()
               if model.expired(ets, now))


def apply(model, args):
    global EXPIRED_ON_DISK
    if getattr(model, "expired_on_disk", None) is None:
        EXPIRED_ON_DISK = model.expired_on_disk = count_expired(
            model, epoch_now())
        print(f"[ttl_compact] the reference holds {EXPIRED_ON_DISK} rows "
              f"expired on disk: ttl_compact_dropped_rows is to read that "
              f"times the replicas of a partition", file=sys.stderr,
              flush=True)
