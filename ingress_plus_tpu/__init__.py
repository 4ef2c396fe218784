"""ingress_plus_tpu — TPU-native WAF detection framework.

A brand-new framework with the capabilities of wallarm/ingress-plus
(Wallarm's ingress-nginx WAF fork), re-designed TPU-first:

- ``compiler/``  — ruleset compiler: SecLang (ModSecurity CRS) / signature
  packs → mandatory-factor extraction → bit-parallel shift-and (bitap) NFA
  tables.  The analog of the reference's closed-source libproton compiled
  ruleset (proton.db) and of libmodsecurity's SecLang engine
  (reference: internal C engines, see SURVEY.md §2.2).
- ``ops/``       — the batched byte-stream scan as XLA programs (the
  reference's per-byte automaton hot loop, SURVEY.md §3.3).
- ``models/``    — detection models: prefilter NFA + per-class verdict heads,
  strict-grammar SQLi/XSS confirm (libdetection analog), ML scorer.
- ``parallel/``  — device-mesh sharding: DP (batch), TP (ruleset shards),
  EP (tenant routing), SP (streaming halo exchange) via shard_map + XLA
  collectives over ICI (SURVEY.md §2.4).
- ``serve/``     — dispatcher/serve loop: batching, fail-open, ruleset
  hot-swap, metrics (the nginx-module/sidecar boundary, SURVEY.md §3.3).
- ``control/``   — control-plane analog: annotations, global config,
  template rendering (SURVEY.md §2.1).
- ``rules/``     — bundled CRS-v3-shaped rule corpus + signature packs
  (authored for this project; provenance in rules/README.md).
"""

__version__ = "0.1.0"
