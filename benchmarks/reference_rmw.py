"""The plain reference of a read-modify-write written as check_and_set:
what a correct store answers to each conditional write of one row.

Imports nothing from pegasus_tpu. The statuses are rocksdb's codes as
the store's rrdb replies carry them, written out here.

One call of the `rmw` op kind (ops/rmw.py) reads its rows, then sends
every RMW as a check_and_set whose operand is the value it read
(`CT_VALUE_BYTES_EQUAL`; `CT_VALUE_NOT_EXIST` where it found no row),
with `return_check_value`. A failed check returns the row's value,
which is the operand of that RMW's next round; all of a call's
unfinished RMWs go out together in the next round. Inside a round the
store applies the writes of one row in submission order, each against
the value the one before it left.
"""

from __future__ import annotations

OK = 0
TRY_AGAIN = 13
ROUNDS = 16     # rounds of check_and_set before an RMW counts as failed


def rmw_rounds(value, new_values, max_rounds: int = ROUNDS):
    """The answers a correct store gives to the RMWs of one row in one
    call, in submission order.

    `value` is the row's value before the call (None: no row), which
    every RMW read; `new_values` are what each sets. Returns a list
    with one entry an RMW: its rounds as (status, value the check saw,
    None for no row), ending in OK unless `max_rounds` ran out; and the
    row's value after the call."""
    operands = [value] * len(new_values)
    rounds = [[] for _ in new_values]
    pending = list(range(len(new_values)))
    for _ in range(max_rounds):
        left = []
        for i in pending:
            if operands[i] == value:
                rounds[i].append((OK, value))
                value = new_values[i]
            else:
                rounds[i].append((TRY_AGAIN, value))
                operands[i] = value
                left.append(i)
        pending = left
        if not pending:
            break
    return rounds, value
