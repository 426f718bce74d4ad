------------------------------ MODULE Smokeraft ------------------------------
\* Companion module of Smokeraft.cfg.  NOT upstream's text letter for
\* letter (no copy of lemmy/raft.tla's Smokeraft.tla is on this machine):
\* it holds what raft_tla_tpu/utils/cfg.py scans a companion module for
\* (k, and StopAfter's two TLCSet("exit", ...) conjuncts) and, as comments,
\* SmokeInit's domains as raft_tla_tpu/models/smoke.py and
\* benchmark/reference/smoke.py read them from SURVEY.md's account of
\* Smokeraft.tla:4-76.  Stock TLC needs upstream's own file.
EXTENDS MCraft, TLC, Randomization

\* Smokeraft.tla:4-9    BoundedSeq(S, n): sequences over S of length <= n;
\*                      SmokeSeq(S) == BoundedSeq(S, 1) for the sequences
\*                      inside messages (mentries, mlog).
\* Smokeraft.tla:11-12  SmokeNat == 0..2
\* Smokeraft.tla:14-15  SmokeInt == -1..1

\* Smokeraft.tla:17-19  the size of every RandomSubset: k^9 initial states
\*                      (1, 512, 19683, 262144 for k = 1..4).
k ==
    2

\* Smokeraft.tla:24-62  SmokeMessageType, the union of four RandomSubset(k, .)
\*   RequestVoteRequest    mterm, mlastLogTerm, mlastLogIndex \in SmokeNat
\*   RequestVoteResponse   mterm \in SmokeNat, mvoteGranted \in BOOLEAN,
\*                         mlog \in SmokeSeq([term : SmokeNat, value : Value])
\*   AppendEntriesRequest  mterm \in SmokeNat, mprevLogIndex \in SmokeInt,
\*                         mprevLogTerm \in SmokeNat,
\*                         mentries \in SmokeSeq([term : SmokeNat, value : Value]),
\*                         mcommitIndex \in SmokeNat
\*   AppendEntriesResponse mterm \in SmokeNat, msuccess \in BOOLEAN,
\*                         mmatchIndex \in SmokeNat
\*   each with msource, mdest \in Server.
\*
\* Smokeraft.tla:64-76  SmokeInit: every variable \in RandomSubset(k, D), so
\*   the initial states are the product of nine k-subsets:
\*   currentTerm    [Server -> SmokeNat]
\*   state          [Server -> {Follower, Candidate, Leader}]
\*   votedFor       [Server -> Server \cup {Nil}]
\*   log            [Server -> BoundedSeq([term : SmokeNat, value : Value], 3)]
\*   commitIndex    [Server -> SmokeNat]
\*   votesResponded [Server -> SUBSET Server]
\*   votesGranted   [Server -> SUBSET Server]
\*   nextIndex      [Server -> [Server -> {n \in SmokeNat : 1 <= n}]]
\*   matchIndex     [Server -> [Server -> SmokeNat]]
\*   messages = one bag of SmokeMessageType, every multiplicity 1, shared
\*   by all initial states (:76).

\* Smokeraft.tla:88-92  a budget, not a state predicate.
StopAfter ==
    /\ TLCSet("exit", TLCGet("duration") > 1)
    /\ TLCSet("exit", TLCGet("diameter") > 100)

==============================================================================
