"""The stacked mask programs (ops/predicates.py
stacked_static_block_predicate, stacked_multi_static_block_predicate_
submit): a chunk of more than one block is ONE jitted call that
concatenates the blocks' columns inside the program. Every block's mask
out of a stack must equal what the single-block program
(static_block_predicate), the independent reference, gives that block
alone: through both waves of server/scan_coordinator.py, for stacks of
2, 7 and 16 blocks (padded to STACK_CHUNK), two key widths in one wave,
hash_lo on every block, on none and on some, validation on and off,
every hash and sort filter type, the blocks of several partitions (one
of them past the partition version) in one cross-partition wave, and
the multi-flavor wave with K = 1 and K = 3 (padded to 4). Blocks of 32
rows on the CPU.
"""

import threading

import jax
import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_POSTFIX,
    FT_MATCH_PREFIX,
    FT_NO_FILTER,
    FilterSpec,
    host_key_hash_lo,
    static_block_predicate,
)
from pegasus_tpu.ops.record_block import build_record_block
from pegasus_tpu.server.scan_coordinator import (
    STACK_CHUNK,
    _eval_cross_partition_multi,
    stacked_block_eval,
)
from pegasus_tpu.server.workload import DRIFT
from pegasus_tpu.utils.metrics import METRICS

CAP = 32
PV = 7                      # eight partitions: pidx & 7
FILTERS = {
    "none": (FT_NO_FILTER, b""),
    "hash_anywhere": (FT_MATCH_ANYWHERE, b"r01"),
    "hash_prefix": (FT_MATCH_PREFIX, b"user01"),
    "hash_postfix": (FT_MATCH_POSTFIX, b"7"),
    "sort_anywhere": (FT_MATCH_ANYWHERE, b"ld3"),
    "sort_prefix": (FT_MATCH_PREFIX, b"field1"),
    "sort_postfix": (FT_MATCH_POSTFIX, b"9"),
}


@pytest.fixture(scope="module", autouse=True)
def drift_left_as_found():
    """Most cases compile a program of their own, and the drift audit
    reads each such wave as a slow kernel: the process-wide gauge this
    module would leave behind fires the health rule `cost_model_drift`
    in a later module of the same process."""
    yield
    DRIFT.reset()


def _block(seed: int, hash_lo: bool, long_keys: bool = False):
    """A device block of 20-32 rows of user%04d / field%d keys (hashkeys
    of 40 bytes where `long_keys`: key width 64), its hash_lo column
    computed on the host, or left out."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, CAP + 1))
    hks = [(b"user%04d" % rng.integers(0, 250))
           + (b"x" * 32 if long_keys else b"") for _ in range(n)]
    sks = [b"field%d" % rng.integers(0, 40) for _ in range(n)]
    blk = build_record_block([generate_key(h, s) for h, s in zip(hks, sks)],
                             [0] * n, capacity=CAP)
    lo = np.zeros(CAP, dtype=np.uint32)
    lo[:n] = host_key_hash_lo(hks, sks)
    blk = blk._replace(hash_lo=lo if hash_lo else None)
    return jax.tree.map(jax.numpy.asarray, blk)


def _blocks(n: int, hash_lo: str, long_every: int = 0):
    """[(tag, block, pidx)]: pidx over 0..7 and one block (the fourth)
    of partition 9, past the version, which matches nothing validated."""
    out = []
    for i in range(n):
        has = {"all": True, "none": False, "mixed": i % 2 == 0}[hash_lo]
        pidx = 9 if i == 3 else i % 8
        out.append((i, _block(1000 * n + i, has,
                              long_keys=bool(long_every)
                              and i % long_every == 1), pidx))
    return out


def _reference(dev, pidx, validate, fkey):
    hft, hfp, sft, sfp = fkey
    return np.asarray(static_block_predicate(
        dev, hash_filter=FilterSpec.make(hft, hfp),
        sort_filter=FilterSpec.make(sft, sfp), validate_hash=validate,
        pidx=pidx, partition_version=PV))


def _stacked_programs() -> int:
    return sum(ent["metrics"]["mask_stacked_programs"]["value"]
               for ent in METRICS.snapshot(entity_type="engine")
               if "mask_stacked_programs" in ent["metrics"])


class _Server:
    """What the multi-flavor wave asks of a partition server."""

    _mask_lock = threading.Lock()
    _warm_flavors = {}

    def __init__(self, pidx):
        self.pidx = pidx
        self.stored = {}

    def store_mask_for(self, ckey, validate, fkey, keep, computed_pv):
        self.stored[(ckey, fkey)] = np.asarray(keep)


def _single_flavor(n, hash_lo, validate, hash_f="hash_prefix",
                   sort_f="sort_prefix", long_every=0):
    return pytest.param(
        dict(kind="single", n=n, hash_lo=hash_lo, validate=validate,
             fkey=FILTERS[hash_f] + FILTERS[sort_f], long_every=long_every),
        id=f"single-{n}-hash_lo_{hash_lo}-validate_{validate}-{hash_f}-"
           f"{sort_f}" + ("-two_widths" if long_every else ""))


def _multi_flavor(n, k, hash_lo="all", validate=True):
    return pytest.param(
        dict(kind="multi", n=n, k=k, hash_lo=hash_lo, validate=validate),
        id=f"multi-{n}-K{k}-hash_lo_{hash_lo}-validate_{validate}")


CASES = (
    [_single_flavor(n, h, v) for n in (2, 7, STACK_CHUNK)
     for h in ("all", "none", "mixed") for v in (True, False)]
    + [_single_flavor(STACK_CHUNK, "all", True, hf, sf)
       for hf in ("none", "hash_anywhere", "hash_prefix", "hash_postfix")
       for sf in ("none", "sort_anywhere", "sort_prefix", "sort_postfix")]
    + [_single_flavor(STACK_CHUNK + 7, "mixed", True, long_every=3)]
    + [_multi_flavor(n, k) for n in (2, 7, STACK_CHUNK) for k in (1, 3)]
    + [_multi_flavor(STACK_CHUNK, 3, "mixed", False)])


@pytest.mark.parametrize("case", CASES)
def test_stacked_masks_equal_the_single_block_program(case):
    blocks = _blocks(case["n"], case["hash_lo"], case.get("long_every", 0))
    validate = case["validate"]
    if case["kind"] == "single":
        before = _stacked_programs()
        got = dict(stacked_block_eval(blocks, validate, PV,
                                      filter_key=case["fkey"]))
        # one stacked program a chunk of more than one block
        widths = {}
        for _t, dev, _p in blocks:
            widths[dev.keys.shape[1]] = widths.get(dev.keys.shape[1], 0) + 1
        assert _stacked_programs() - before == sum(
            c // STACK_CHUNK + (c % STACK_CHUNK > 1)
            for c in widths.values())
        assert sorted(got) == [t for t, _d, _p in blocks]
        for tag, dev, pidx in blocks:
            want = _reference(dev, pidx, validate, case["fkey"])
            np.testing.assert_array_equal(got[tag], want, err_msg=str(tag))
        return
    patterns = [b"user00", b"user01", b"user1"][:case["k"]]
    fkeys = [(FT_MATCH_PREFIX, p, FT_NO_FILTER, b"") for p in patterns]
    servers = {p: _Server(p) for _t, _d, p in blocks}
    states = {fk: {"cached_keep": {}} for fk in fkeys}
    _eval_cross_partition_multi(
        {fk: [(servers[p], states[fk], tag, dev) for tag, dev, p in blocks]
         for fk in fkeys}, validate, PV)
    for fk in fkeys:
        assert sorted(states[fk]["cached_keep"]) == [t for t, _d, _p in blocks]
        for tag, dev, pidx in blocks:
            want = _reference(dev, pidx, validate, fk)
            np.testing.assert_array_equal(servers[pidx].stored[(tag, fk)],
                                          want, err_msg=str((tag, fk)))
            np.testing.assert_array_equal(states[fk]["cached_keep"][tag],
                                          want)
