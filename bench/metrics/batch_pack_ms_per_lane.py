"""The engine's batch pack (padding the lanes to one shape and the
copies to the device): its ``simjax.pack_batch`` host span per lane of
the traced sweep cells."""


def read(m):
    spans = (m.trace or {}).get("program_spans", {})
    if "simjax.pack_batch" not in spans or not m.traced_cells:
        return None
    return 1e3 * spans["simjax.pack_batch"] / (m.lanes * m.traced_cells)
