"""The background ``import orbax.checkpoint``'s own wall seconds
(``bootstrap.initialize`` starts it before the attach; the blob's
``setup_overlapped.ckpt_import``). Beside set-up's spans, in none of them:
against ``ckpt_open_s`` it says how much of the import the start hid."""


def read(r):
    stats = r["report"].get("stepstats") or {}
    return (stats.get("setup_overlapped") or {}).get("ckpt_import")
