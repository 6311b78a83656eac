"""The share of the rows the expert layers computed that some token
selected, in percent: the program's counters ``moe.pairs_held`` (token-
expert selections that went to a held expert) over ``moe.expert_rows``
(rows x held experts the expert layers ran: every row of a decode step
through every held expert, counted on the host from the steps it
dispatched), both published when the server drains its device-side
counts at ``close()`` (since the ramp began, the decode steps alone).  What the every-row form of the expert layer computes that no
token asked for is the rest.

None where the program publishes no such counter: the line leaves the
metric out."""


def read(run: dict, args: dict):
    try:
        from paddle_tpu import telemetry
    except ImportError:
        return None
    c = telemetry.snapshot().get("counters", {})
    rows = c.get("moe.expert_rows")
    if not rows:
        return None
    return 100.0 * c.get("moe.pairs_held", 0) / rows
