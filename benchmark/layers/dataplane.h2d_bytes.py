"""Bytes the traced search sent from the host to the device."""


def read(ctx):
    plane = ctx["report"].get("dataplane")
    if not plane or not plane.get("enabled"):
        return None
    return plane["bytes_uploaded"] + plane.get("bytes_staged", 0)
