"""Token-sequence policies: a decoder trunk trained as next-token
prediction over packed episodes (instruction, proprioception and
discretised action tokens)."""
