"""Share of device busy time spent moving the KV pool. Two parts are counted
and a traced run prints each, with the two that are not:

- `scoped`: everything under `kv_gather` (the per-layer page gather) and
  `kv_write` (the token's scatter), names of the program's vocabulary;
- `scan`: the layer scan's own movement of the pool, sliced a layer at a
  time and written back whole as the scan's output. The program gives these
  no scope: their paths name the scan and a jaxpr primitive
  (`<program>/while/body/squeeze`, `/dynamic_slice`,
  `/dynamic_update_slice`), which is JAX's name and can change with it, and
  the layer weights' slices carry the same paths. The pool is told from the
  weights by the type of the instruction's result: a block of pages ends in
  the cell's own (page size, KV heads, head size);
- `scan_other` (not counted): the same paths with any other result, the
  weights' slices;
- `unnamed` (not counted): operations with no path at all whose result is
  a block of pages, the copies of the whole pool the compiler adds.

A compiler or JAX change that renames the scan's movement shows as `scan`
falling and `unnamed` rising by as much."""

from benchmark import scopes

LAYER = "serving engine decode tick"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"

POOL_SCOPES = frozenset(("kv_gather", "kv_write"))
SCAN_OPERANDS = frozenset(("squeeze", "dynamic_slice", "dynamic_update_slice"))


def page_block(cell) -> list:
    """The trailing sizes of any array of whole pages in this cell."""
    model = cell.model
    return [str(n) for n in (
        cell.params["engine"]["page_size"], model["num_key_value_heads"],
        model["hidden_size"] // model["num_attention_heads"])]


def part_of(op, block: list):
    """`scoped`, `scan`, `scan_other`, `unnamed` or None (not the pool's)."""
    if scopes.under(op.path, POOL_SCOPES):
        return "scoped"
    of_pages = op.result[op.result.find("[") + 1:-1].split(",")[-3:] == block
    parts = scopes.components(op.path)
    if (len(parts) >= 3 and parts[-3:-1] == ["while", "body"]
            and parts[-1] in SCAN_OPERANDS):
        return "scan" if of_pages else "scan_other"
    return "unnamed" if of_pages and not parts else None


def read(obs: dict):
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    block = page_block(obs["cell"])
    shares = scopes.shares_by(trace, lambda op: part_of(op, block))
    print("kv_pool_share.serve parts, % of busy time: " + ", ".join(
        f"{part} {shares.get(part, 0.0):.2f}"
        for part in ("scoped", "scan", "scan_other", "unnamed"))
        + " (the first two are counted)", flush=True)
    return shares.get("scoped", 0.0) + shares.get("scan", 0.0)
