"""Share of the rows the grouped expert kernel computed in the window that
are tile padding: 1 - assignments routed to held experts / rows computed,
from the step's counters (each expert's rows are padded to a whole row
tile).  A program whose step has no such counters reads nothing."""


def read(ctx):
    st = ctx.stats
    rows = st.get("moe_rows_computed")
    if not rows:
        return None
    return 100.0 * (1.0 - st["moe_assignments"] / rows)
