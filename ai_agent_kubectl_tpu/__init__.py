"""ai_agent_kubectl_tpu — a TPU-native natural-language → kubectl framework.

A ground-up rebuild of the capabilities of ``mrankitvish/ai-agent-kubectl``
(reference: ``/root/reference/app.py``): an HTTP service that translates
natural-language queries into validated single-line ``kubectl`` commands and
optionally executes them — with the reference's remote OpenAI ChatCompletion
call replaced by an in-tree JAX/XLA/Pallas inference engine running entirely
on TPU.

Package layout:

- ``config``    — typed env-var configuration (reference: app.py:23-36)
- ``server``    — HTTP API, auth, rate limiting, caching, metrics, execution
                  (reference: app.py:60-400)
- ``engine``    — the inference engine that replaces the remote LLM call
                  (reference seam: app.py:117,184): tokenizer, KV caches,
                  batching scheduler, jit prefill/decode
- ``models``    — pure-JAX decoder-only transformer families (Gemma, Llama,
                  Mixtral) and weight conversion
- ``ops``       — Pallas TPU kernels (flash attention, ragged pool
                  attention, ring attention) and numeric reference ops
- ``parallel``  — device mesh construction, NamedSharding policies (DP/TP/
                  EP/SP), multi-host initialization
- ``utils``     — profiling, watchdog, misc
"""

__version__ = "0.1.0"
