"""Round programs staged per ``repro.solve`` call (programs/call): the
count of the program's ``solve.compile`` spans over its ``solve`` spans
in the window.  1.0 while every call stages its program anew; 0 once a
call reuses a staged program."""
from bench.spans import counts


def read(rec):
    n = counts(rec["trace"], ("solve", "solve.compile"))
    return n["solve.compile"] / n["solve"] if n["solve"] else None
