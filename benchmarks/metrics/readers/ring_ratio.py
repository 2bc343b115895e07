"""Engine phase ring: a ratio of sums over the window's records,
`scale` x sum(num) / sum(den).

`over` says what is summed: the `iterations` (one record each) or the
`requests` (the entries an iteration record lists under `requests`: the
sequences whose first token came out of that iteration).  A term is a list
of keys of one item, multiplied together; a key written `-key` enters with
the opposite sign.  So `[["swap_s", "blocked_slots"]]` is the slot-seconds
that admissions held up, and `[["prompt_tokens"], ["-shared_tokens"]]` the
tokens a prefill had to compute.

A ring without these keys — a program from before the engine recorded
them — is nothing to read: None, and the line leaves the metric out."""


def _total(items, terms):
    total = 0.0
    for item in items:
        for term in terms:
            v = 1.0
            for key in term:
                v *= -item[key[1:]] if key.startswith("-") else item[key]
            total += v
    return total


def read(obs, params, ctx):
    ring = obs["serve"]["ring"]
    try:
        items = ring if params["over"] == "iterations" else [
            q for r in ring for q in r["requests"]]
        num, den = _total(items, params["num"]), _total(items, params["den"])
    except KeyError:
        return None
    return params["scale"] * num / den if den else None
