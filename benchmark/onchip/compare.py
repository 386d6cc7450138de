"""Holding a cell's compared numbers to its limits.

What is compared, and how the numbers are made from the program's record
and the plain reference's, is ``comparisons/<name>.py``, which the cell's
traffic file names; the limits, with the readings they were set between,
are ``limits/<cell>.json``.
"""


def verdict(nums, limits):
    """(correct, rows) where rows are (name, value, limit, ok).  Every
    number in ``limits`` has to be there and at or under its limit; a
    cell with no limits file is not correct."""
    if not limits:
        return False, [(k, v, None, False) for k, v in nums.items()]
    rows = []
    for name, limit in limits["limits"].items():
        value = nums.get(name, float("nan"))
        rows.append((name, value, limit, bool(value <= limit)))
    return all(r[3] for r in rows), rows
