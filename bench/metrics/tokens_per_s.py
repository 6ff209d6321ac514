"""tokens_per_s: prompt tokens of the prefills whose logits reached the
host inside the window, over the window's length."""


def read(run):
    return run.items_per_s() if run.model.ITEM == "tokens" else None
