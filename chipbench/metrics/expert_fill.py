"""Share of the rows the held experts computed that carried a token's
pick, in percent, over the traced steps: the program's
``moe.assignments_here`` over ``moe.expert_rows`` (padding included; with
no token dropped each held expert computes every row of a dispatch)."""


def read(r):
    here, rows = r.data.get("moe_assignments_here"), r.data.get(
        "moe_expert_rows")
    if not rows:
        return None
    return 100.0 * here / rows
