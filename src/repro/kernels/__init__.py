"""Pallas TPU kernels for the perf-critical hot spots.

vq_assign        — Eq. 10 nearest-cluster search over 16K-32K clusters
                   (MXU matmul + online running-(min,argmin) over K blocks)
inbatch_softmax  — fused L_aux/L_ind in-batch CE (online logsumexp,
                   (B,B) logits never hit HBM)
topk_dot         — retrieval_cand: fused 1xD * Dx1M scoring + two-stage
                   top-k
cluster_rank     — serving indexing step: blocked u.e_k scoring + online
                   top-n over the codebook (Eq. 5/11)
merge_serve      — serving Alg. 1: batched k-way chunked merge, head
                   pointers in registers, one-pass top-S emission
embedding_bag    — fused gather+reduce over HBM-resident tables (scalar-
                   prefetch indices + per-row DMA)
flash_attention  — causal flash attention (LM train/prefill hot spot)

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling, a jit'd
wrapper in ops.py (Mosaic on a TPU, interpret mode on the CPU backend,
an error on any other), and a pure-jnp oracle in ref.py; tests sweep
shapes/dtypes against the oracle, and tests/test_tpu_compile.py compiles
the retriever's kernels for a described v5e chip at full width.
"""
from repro.kernels import ops, ref
