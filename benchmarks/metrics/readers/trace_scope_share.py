"""Self time of the device operations traced under the `jax.named_scope`s
`scopes`, over the chip's busy time.  The seconds by scope are the
driver's (`obs["serve"]["scopes"]`, benchmarks/trace/scopes.py: the trace
itself does not show a scope); a run without them — a program without
these scopes, or a driver that does not collect them — is nothing to
read."""


def read(obs, params, ctx):
    by_scope = obs["serve"].get("scopes")
    busy = obs["trace"]["busy_s"]
    if not by_scope or not busy:
        return None
    s = sum(by_scope.get(k, 0.0) for k in params["scopes"])
    return 100.0 * s / busy if s else None
