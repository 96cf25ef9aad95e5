"""Sparse logistic regression over the hashed table with AdaGrad: Wide&Deep's
arithmetic with no deep side (``embed_dim`` absent, no ``hidden_dims``), so
everything comes from that model's file. This file exists only in the tests'
copy of the benchmark: a model is added as a file, with no edit to another."""

from lib import jobs

_wd = jobs.load_model("widedeep")
Adapter, layout = _wd.Adapter, _wd.layout
flops_per_item, bytes_per_item = _wd.flops_per_item, _wd.bytes_per_item
