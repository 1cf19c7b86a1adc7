"""RDF-like triples: the repository's own seeded stand-in for the ITR
paper's Table 1b RDF datasets (``repro.data.synthetic.rdf_like``: Zipf
predicates, small subject stars, mostly leaf objects). At a dataset's
published counts it is the graph ``PAPER_DATASETS[name](scale=1.0, seed)``
makes."""
from __future__ import annotations

from repro.data.synthetic import rdf_like


def generate(seed: int, n_nodes: int, n_triples: int, n_preds: int):
    """(triples int64[n, 3] sorted and unique, n_nodes, n_preds)."""
    ds = rdf_like(n_nodes, n_triples, n_preds, seed)
    return ds.triples, int(ds.n_nodes), int(ds.n_preds)
