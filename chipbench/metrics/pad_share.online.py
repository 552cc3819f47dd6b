"""Share of the rows the server executed in the window that were padding
(``BucketReport.padded`` over executed rows)."""


def read(ctx):
    c = ctx.counts
    rows = c["images"] + c["padded"]
    return 100.0 * c["padded"] / rows if rows else None
