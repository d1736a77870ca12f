"""Residency: device memory in use after the window, fullest chip, as a
share of its limit (``/debug/devices``)."""


def read(cap):
    shares = [d["bytesInUse"] / d["bytesLimit"]
              for d in cap.devices_after["devices"]
              if d.get("bytesInUse") is not None and d.get("bytesLimit")]
    return 100.0 * max(shares) if shares else None
