"""The program's ``dataPreparation/copyToDevice`` span of the traced fit:
the float32 table from the host to the device, until the copy is ready."""


def read(ctx):
    spans = ctx["entry"].spans[0] if ctx["entry"].spans else {}
    if "dataPreparation/copyToDevice" not in spans:
        return None
    return spans["dataPreparation/copyToDevice"] * 1e3
