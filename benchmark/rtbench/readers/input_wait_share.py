"""Share of the window the step waited for its batch (the ``input_wait_s``
the train loop reports through ``session.report``, which feeds the goodput
ledger's ``input_wait`` phase)."""


def read(obs, params):
    if obs.get("kind") != "train":
        return None
    return 100.0 * obs["input_wait_s"] / obs["window_s"]
