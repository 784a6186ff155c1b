"""Faults planted under the timed path, for the control and for
tests/test_faults.py: each has to make a run come out not correct.
run.py never plants one.

  lost_write      an acknowledged write that was never stored: one row
                  in 997 of the load, the first insert of every
                  write_multi call (the durability the configs state)
  half_batch      half of a read call's operations left out and
                  answered as empty
  altered_answer  one byte of one value altered in one read call in 7
  lost_reply      one batched read call in 7 never answered (it raises
                  the client's timeout)
  no_group_check  the exchange that tells the secondaries of a commit
                  (the primaries' group_check messages) left out once
                  the table is loaded
"""

from __future__ import annotations

FAULTS = ("lost_write", "half_batch", "altered_answer", "lost_reply",
          "no_group_check")


def drops_loaded_row(fault, n: int) -> bool:
    return fault == "lost_write" and n % 997 == 0


def plant_in_cluster(sim, fault) -> None:
    """The faults that sit in the cluster, not under the client."""
    if fault != "no_group_check":
        return
    send = sim.net.send

    def send_but_group_check(src, dst, msg_type, payload):
        # inter-node traffic rides a ("replica", {gpid, type, payload})
        # envelope
        if not (msg_type == "replica"
                and payload.get("type") == "group_check"):
            send(src, dst, msg_type, payload)

    sim.net.send = send_but_group_check


def _flip(value: bytes) -> bytes:
    return bytes([value[0] ^ 1]) + value[1:]


class _Faulty:
    def __init__(self, client, fault: str):
        self._client, self._fault, self._calls = client, fault, 0

    def __getattr__(self, name):
        return getattr(self._client, name)   # every other call, unharmed

    def _tick(self) -> bool:
        self._calls += 1
        return self._calls % 7 == 0

    def write_multi(self, groups):
        if self._fault != "lost_write":
            return self._client.write_multi(groups)
        first = next(iter(groups))
        kept = {p: ops[1:] if p == first else ops
                for p, ops in groups.items()}
        out = self._client.write_multi({p: o for p, o in kept.items() if o})
        return {p: ([0] if p == first else []) + out.get(p, [])
                for p in groups}

    def _maybe_lose(self) -> None:
        from pegasus_tpu.utils.errors import ErrorCode, PegasusError

        if self._fault == "lost_reply" and self._tick():
            raise PegasusError(ErrorCode.ERR_TIMEOUT, "reply lost (planted)")

    def scan_multi(self, groups):
        from pegasus_tpu.server.types import KeyValue, ScanResponse

        self._maybe_lose()
        if self._fault == "half_batch":
            kept = {p: reqs[:len(reqs) // 2] for p, reqs in groups.items()}
            out = self._client.scan_multi(
                {p: r for p, r in kept.items() if r})
            return {p: out.get(p, []) + [
                ScanResponse() for _ in reqs[len(reqs) // 2:]]
                for p, reqs in groups.items()}
        out = self._client.scan_multi(groups)
        if self._fault == "altered_answer" and self._tick():
            for resps in out.values():
                for resp in resps:
                    if len(resp.kvs):
                        kvs = list(resp.kvs)
                        kvs[0] = KeyValue(kvs[0].key, _flip(kvs[0].value))
                        resp.kvs = kvs
                        return out
        return out

    def point_read_multi(self, groups):
        self._maybe_lose()
        if self._fault == "half_batch":
            kept = {p: ops[:len(ops) // 2] for p, ops in groups.items()}
            out = self._client.point_read_multi(
                {p: o for p, o in kept.items() if o})
            return {p: out.get(p, []) + [(1, b"")] * (len(ops) - len(ops) // 2)
                    for p, ops in groups.items()}
        out = self._client.point_read_multi(groups)
        if self._fault == "altered_answer" and self._tick():
            for p, results in out.items():
                for i, (err, value) in enumerate(results):
                    if err == 0 and value:
                        results[i] = (err, _flip(value))
                        return out
        return out


def wrap_client(client, fault):
    if fault in (None, "no_group_check"):
        return client
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return _Faulty(client, fault)
