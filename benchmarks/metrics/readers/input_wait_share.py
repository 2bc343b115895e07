"""Share of the window the train loop spent inside next(batches)."""


def read(obs, params, ctx):
    return 100.0 * obs["train"]["input_wait_s"] / obs["window_s"]
