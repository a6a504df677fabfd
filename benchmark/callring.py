"""The program's device-call ring (`StepTrace.calls()`: one record a device
call whose output the host reads, with the stamps of its launch and of its
blocking read), found as `steprings` finds the step ring and cut to the same
window. A program that keeps no call ring (the parent of the PR that added
it, or no recorder at all) gives None, and the readers leave their metric
out."""
import steprings


def ring(subsystem):
    """The newest recorder of `subsystem` if it keeps a call ring."""
    found = steprings._ring(subsystem)
    return found if hasattr(found, "calls") else None


def calls(obs, subsystem):
    """Call records READ inside the window, in the order they were read;
    None where the program keeps none."""
    found = ring(subsystem)
    return None if found is None else found.calls(*steprings.window(obs))
