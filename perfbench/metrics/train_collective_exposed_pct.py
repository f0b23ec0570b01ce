"""Device trace: collective time during which no compute ran on that device,
over the device's busy time."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("collective_s"):
        return None
    return 100.0 * (tr["collective_s"] - tr["collective_hidden_s"]) \
        / tr["busy_s"]
