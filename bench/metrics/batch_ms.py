"""Host time per call of the ``batch_fn`` that ``run_training`` calls, in ms:
the harness's span around it, over the window."""


def read(r):
    calls = r.spans.calls.get("batch", 0)
    return 1e3 * r.spans.seconds["batch"] / calls if calls else None
