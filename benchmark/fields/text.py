"""A ``text`` field: the copied MS MARCO-shaped generator (corpus.py)."""

from benchmark.corpus import build_corpus


def build(rng, n: int, spec: dict) -> dict:
    return build_corpus(rng, n, spec["vocab"], spec["avg_len"],
                        spec["burst"], spec["zipf"])


def mapping(spec: dict) -> dict:
    return {"type": "text"}
