"""images_per_s: images whose logits reached the host inside the window,
over the window's length."""


def read(run):
    return run.items_per_s() if run.model.ITEM == "images" else None
