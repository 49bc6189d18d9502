"""Due to first token, mean over all the requests due in the window."""


def read(ctx):
    return ctx.record["end_to_end"].get("serve_ttft_mean")
