"""Plain float32 references of what each cell's timed path computes.

They import nothing of the program under test and take nothing it made:
the data and the served factors come from ``bench/gen``.  Every matmul runs
at ``highest`` precision, so on a TPU a float32 product is a float32
product.  ``dtype`` (float32 by default) lets ``bench/controls.py`` run the
same reference one precision lower, the control that must fail the check.
"""
