"""The program's ``dataPreparation/binning`` span of the traced fit:
``apply_bins`` on the device copy, until the binned matrix is ready."""


def read(ctx):
    spans = ctx["entry"].spans[0] if ctx["entry"].spans else {}
    if "dataPreparation/binning" not in spans:
        return None
    return spans["dataPreparation/binning"] * 1e3
