"""Op kind `compact`: the operator's manual compaction of a table
under its `user_specified_compaction` rules, as tools/shell.py's
`manual_compact` and `set_app_envs` do it: one admin `update_app_envs`
to the meta through the client's transport, carrying the mix's
`app_envs` (the ruleset, as the config states it) and
`manual_compact.once.trigger_time` = now. The meta propagates the envs
to the nodes, each replica that hears a trigger newer than its last
compacts on the program's own threads. The op is done when the meta
has acknowledged; it calls no compaction itself.

The deployment's operator bounds the compactions that run at once
(`manual_compact.max_concurrent_running_count`, stated by the config
and sent with every trigger). A program that does not know that env
starts a thread a replica at every trigger, 192 at once: that is
another deployment, not this one run slower, and its numbers (a few
hundred ops/s, spreading by more than their median) guard nothing. So
the kind refuses such a program when the harness imports it, before
the cluster is built: the run ends with a non-zero exit code within
seconds and prints no result.

args = (table, {env: text}); reply = True.
"""

import itertools
import json
import time

BOUND_ENV = "manual_compact.max_concurrent_running_count"


def program_bounds_compactions() -> bool:
    """Whether the program has the pool that BOUND_ENV bounds (the
    same question readers/compact_window.py asks)."""
    from pegasus_tpu.storage import compact_governor

    return hasattr(compact_governor, "MANUAL_COMPACT_POOL")


if not program_bounds_compactions():
    raise SystemExit(
        f"op kind `compact`: this program does not know the table env "
        f"{BOUND_ENV}, which the configuration states: it cannot run "
        f"the deployment")

_RIDS = itertools.count(1)
_REPLIES = {}        # (transport, endpoint) -> {rid: admin_reply}
PUMP_ROUNDS = 64


def env_text(value) -> str:
    """An env's value as the meta stores it: text; a JSON value (the
    ruleset) in its canonical spelling."""
    if isinstance(value, str):
        return value
    return json.dumps(value, sort_keys=True, separators=(",", ":"))


def draw(rng, shape_rng, n, spec, ctx):
    envs = {k: env_text(v) for k, v in spec["app_envs"].items()}
    return [(spec["table"], envs)] * n


def _endpoint(client):
    """The operator's address on the client's transport, registered
    once a transport, and the replies that came back to it."""
    name = client.name + ".operator"
    replies = _REPLIES.get((client.net, name))
    if replies is None:
        replies = _REPLIES[(client.net, name)] = {}

        def on_message(src, msg_type, payload):
            if msg_type == "admin_reply":
                replies[payload["rid"]] = payload

        client.net.register(name, on_message)
    return name, replies


def send(client, batch, ctx):
    name, replies = _endpoint(client)
    out = []
    for table, envs in batch:
        t0 = time.perf_counter()
        rid = next(_RIDS)
        envs = dict(envs)
        envs["manual_compact.once.trigger_time"] = str(int(time.time()))
        client.net.send(name, client.meta_addrs[0], "admin", {
            "rid": rid, "cmd": "update_app_envs",
            "args": {"app_name": table, "envs": envs}})
        for _ in range(PUMP_ROUNDS):
            if rid in replies:
                break
            client._pump()
        reply = replies.pop(rid, None)
        ok = reply is not None and reply["err"] == 0
        out.append((True if ok else None, time.perf_counter() - t0))
    return out


def check(model, args, reply, now):
    return None if reply is True else f"compact of {args[0]!r}: {reply!r}"


def apply(model, args):
    """From here on a page may come without the rows a delete rule
    matches (ops/scan_rules.py)."""
    from benchmarks.ops.scan_rules import count_matched_live, rules_state

    state = rules_state(model)
    if state["rules"] is None:
        from benchmarks.reference_rules import Rules

        state["rules"] = Rules(args[1]["user_specified_compaction"])
        count_matched_live(model)
    state["triggered"] = True


def readback(args):
    return []
