"""``scope_ms_per_count`` over ``per`` of its count: device milliseconds of
the operations under some named scopes inside some programs, per ``per`` of
what the program counted as it dispatched them (``per`` 1000 with the count
``tokens`` of ``engine.prefill_dispatch``: milliseconds a thousand prefilled
tokens, the unit ``prefill_ms_per_ktok`` has for the whole program).
``scope_ms_per_count`` has no such parameter and is not this PR's to edit;
this reader multiplies what it returns. None where it gives None."""

from rtbench.readers import scope_ms_per_count


def read(obs, params):
    value = scope_ms_per_count.read(obs, params)
    return None if value is None else value * params.get("per", 1)
