"""Device time in the operations the program itself names, by
`jax.named_scope`, over the device's busy time in the traced window.

The trace's events carry the compiler's instruction names and no scope; the
program publishes `instruction -> scope` for the step programs it built
(`StepTrace.device_scopes()`: rows of program, instruction name, opcode,
custom-call target, result type, scope, backward, straddles, read from each
compiled program's own `op_name` metadata). A row is joined to the trace by
the label `xplane.parse_op` builds for the event of the same instruction. A
label whose rows agree on a scope is booked to that scope; one that two
programs put under DIFFERENT scopes to `(ambiguous)`; one with no row, or
with a row the program gave no scope, to `(unnamed)`.

`args`: `subsystem` ("serving" | "train"); `scopes`, a list of scope paths,
each matched where it begins at a name of the row's path (`moe/router`
matches `mlp/moe/router`, `attn` does not match `attn_full`), `"*"` = any
named scope; optionally `backward` (true: the backward pass's instructions
alone; false: the others) and `programs` (the rows of these step programs
alone). The value is 100 x the matched seconds over `trace["busy_s"]`, what
`kernel_time_share` divides by. None where the program has no table (a
program older than the table, an engine that built no whole-step program)."""
import steprings
import xplane

UNNAMED, AMBIGUOUS = "(unnamed)", "(ambiguous)"


def label(row):
    """The label `xplane.reduce_planes` gives the trace event of `row`'s
    instruction."""
    text = f"%{row.name} = {row.result} {row.opcode}("
    if row.target:
        text += f'), custom_call_target="{row.target}"'
    return xplane.parse_op(text)[0]


def book(rows, programs=None):
    """label -> (scope, backward, straddles) of the rows that stand under it,
    `scope` AMBIGUOUS where they disagree on it or on the pass."""
    booked = {}
    for row in rows:
        if programs is not None and row.program not in programs:
            continue
        key = label(row)
        entry = (row.scope or UNNAMED, row.backward, row.straddles)
        seen = booked.setdefault(key, entry)
        if seen[:2] != entry[:2]:
            booked[key] = (AMBIGUOUS, False, False)
    return booked


def matches(scope, prefixes):
    if scope in (UNNAMED, AMBIGUOUS):
        return False
    return any(p == "*" or f"/{p}/" in f"/{scope}/" for p in prefixes)


def table(subsystem):
    """The rows of the newest recorder of `subsystem`, or None."""
    ring = steprings._ring(subsystem)
    if ring is None or not hasattr(ring, "device_scopes"):
        return None
    return ring.device_scopes() or None


def read(obs, trace, args):
    if trace is None or not trace["busy_s"]:
        return None
    rows = table(args["subsystem"])
    if rows is None:
        return None
    booked = book(rows, args.get("programs"))
    want = args.get("backward")
    seconds = 0.0
    for name, secs in trace["ops"].items():
        scope, backward, _straddles = booked.get(name, (UNNAMED, False, False))
        if matches(scope, args["scopes"]) and want in (None, backward):
            seconds += secs
    return 100.0 * seconds / trace["busy_s"]
