"""Share of the chip's busy time spent extracting patches (im2col), over
device busy time.  im2col is a one-hot convolution, the forward's only
convolution outside the SAC kernel (a Mosaic custom call); on the TPU it
compiles to an output fusion, so its ops inside ``jit_fwd`` runs are those
whose HLO text is a ``convolution`` or a ``kind=kOutput`` fusion."""


def is_patch_copy(op):
    return (" convolution(" in op.name or "kind=kOutput" in op.name) \
        and "tpu_custom_call" not in op.name


def read(record, **_):
    s = record.get("summary")
    if s is None or not s.has_device or s.busy_s <= 0:
        return None
    conv = s.op_seconds(is_patch_copy, within="fwd")
    return 100.0 * conv / s.busy_s if conv > 0 else None
