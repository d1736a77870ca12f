"""Residency: entries evicted during the window (``/debug/devices``
``residency.evictions``; 0 while the index fits)."""


def read(cap):
    try:
        return float(cap.devices_after["residency"]["evictions"]
                     - cap.devices_before["residency"]["evictions"])
    except KeyError:
        return None
