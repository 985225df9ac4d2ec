"""Experts: share of the (token, expert layer) pairs of the window none of
whose 8 routed choices is an expert this chip holds (the program's device
counter ``moe_unheld_tokens``): those tokens get the shared expert alone."""


def read(run):
    c = run.get("counters") or {}
    config = run.get("config") or {}
    if "moe_unheld_tokens" not in c or not c.get("useful_tokens_total"):
        return None
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return 100.0 * float(c["moe_unheld_tokens"]) / (
        c["useful_tokens_total"] * layers)
