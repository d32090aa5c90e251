"""Process start to the first timed step: imports, weights, optimizer state,
compilation or compile-cache loads, and the first steps.  The comparison's
own readings of the program's state are left out."""


def read(run):
    return run["setup_s"]
