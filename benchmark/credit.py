"""Whose window a closed loop's tokens belong to: a request is credited
where its tokens are PRODUCED, so a request in flight at a mark of the window
is split there — what it had by the mark to the side before, the rest to the
side after — and no mark has to be sited between completions; a mark that
falls inside a scheduler step reads between the step's two returns
(``between``), so nor does it matter which step crosses it.  Pure Python.

The benchmark counts: a request's credits over (before, inside, after) sum
to exactly ``len(prompt) + max_new_tokens``, whatever is read.  What the
program reports at a mark (tokens generated so far; its cumulative count of
prefill chunk rows) only places the split, and a reading past a request's
size is clipped: an inflated counter can move a straddler's real tokens
across a mark and add none.
"""


def progress(sizes, generated, prefill_tokens, chunk):
    """How many of its tokens each request submitted so far has, at a mark.

    ``sizes``: ``[(prompt_len, max_new)]`` in submission order, which is the
    order the engine prefills in (first in, first out, one prompt's chunk
    rows before the next one's).  ``generated``: beside it, the tokens each
    has generated (``max_new`` once it completed; ``None`` where it failed:
    such a request is credited nothing).  A request with a token has its
    whole prompt.  One without is apportioned the engine's cumulative count
    of prefilled tokens ``prefill_tokens`` (whole chunk rows of ``chunk``,
    counted from the first submission): what the requests ahead of it have
    not used is its rows, and its prompt counts by the share of its rows."""
    out, left = [], max(prefill_tokens, 0)
    for (prompt, new), got in zip(sizes, generated):
        rows = -(-prompt // chunk) * chunk
        mine = min(rows, left)
        left -= mine
        if got is None:
            out.append(0)
        elif got > 0:
            out.append(prompt + min(got, new))
        else:
            out.append(prompt * mine // rows)
    return out


def between(before, after, share):
    """A ``progress`` reading at a mark that fell inside a scheduler step,
    ``share`` of the step's time after the reading ``before``: each request
    is taken to have gained that share of what the step gave it (whole
    tokens, rounded down).  A request submitted after ``before`` had
    nothing then."""
    before = list(before) + [0] * (len(after) - len(before))
    return [a + int(max(b - a, 0) * share) for a, b in zip(before, after)]


def split(sizes, at_open, at_close):
    """``(before, inside, after)``: the tokens of ``sizes`` (as above) by
    where the two marks' ``progress`` readings put them.  A request
    submitted after a mark has no reading there and had nothing by it.
    Readings are held to ``0 <= at_open <= at_close <= size``."""
    before = inside = after = 0
    for i, (prompt, new) in enumerate(sizes):
        size = prompt + new
        a = min(max(at_open[i], 0), size) if i < len(at_open) else 0
        b = min(max(at_close[i], a), size) if i < len(at_close) else a
        before, inside, after = before + a, inside + b - a, after + size - b
    return before, inside, after
