"""A model's layer pattern as data: which runs of its layer list repeat, so
that a stack of several KINDS of layer is scanned a period at a time, every
position of the period traced for its own kind — no `lax.cond` over kinds in
a loop body, so a carried cache is still touched by Mosaic calls only.

`repeated_runs` splits a list of kinds (any hashable: a letter, a pair of
names) into consecutive runs, each a unit and how often it repeats (the
Nemotron-H family's `hybrid_override_pattern`: "MEMEMEM*EMEMEMEM*..." is
("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ...). K-EXAONE's split
(`models/exaone_moe.py::layer_plan`: listed layers, then whole periods of a
unit whose LENGTH the configuration gives, a single period included) is a
different rule and stays with its model: its one-chip cut, a dense layer and
ONE period window, window, window, full, would come out of this one as a scan
over the three window layers, another program and another parameter tree.
"""


def repeated_runs(kinds):
    """-> [(unit, repeats), ...] whose concatenation is `kinds`: from the
    front, the unit (repeated at least twice) that covers the most layers,
    the shorter unit among equals; a layer nothing repeats is a run of its
    own."""
    kinds = list(kinds)
    runs, at = [], 0
    while at < len(kinds):
        best = (1, 1)                               # (unit length, repeats)
        for length in range(1, (len(kinds) - at) // 2 + 1):
            unit, repeats = kinds[at:at + length], 1
            while kinds[at + repeats * length:
                        at + (repeats + 1) * length] == unit:
                repeats += 1
            if repeats > 1 and length * repeats > best[0] * best[1]:
                best = (length, repeats)
        runs.append((kinds[at:at + best[0]], best[1]))
        at += best[0] * best[1]
    return runs
